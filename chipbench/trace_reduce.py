"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
the device operations that took most time, and the longest idle gaps with
the host span that covers each.

A device plane is one named ``/device:TPU:<n>``.  Its operations are the
events of its ``XLA Ops`` line: one event per HLO operation or Pallas
kernel that ran, so their union is the time in which the chip did work.
Host spans are the events of the host plane whose names start with one of
``HOST_PREFIXES``: the server's ``serve.*`` spans (written into the trace
as ``TraceAnnotation``s) and the benchmark's own ``chipbench.*`` spans.
The traced window is the host span named ``WINDOW_SPAN``.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("serve.", "chipbench.")
WINDOW_SPAN = "chipbench.window"


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), on the trace's one clock."""
    devices: dict[str, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    # "%fusion.3 = f32[...] fusion(...)": the HLO name
                    evs.extend((e.name.split(" = ", 1)[0], e.start_ns,
                                e.end_ns) for e in line.events)
        else:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return Trace(devices, host)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, w0: float, w1: float):
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def window(trace: Trace) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    evs = [(s, e) for d in trace.devices.values() for _, s, e in d]
    if not evs:
        raise ValueError("trace has no window span and no device operation")
    return min(s for s, _ in evs), max(e for _, e in evs)


def covering_span(trace: Trace, s: float, e: float) -> str:
    """The host span that overlaps [s, e] the most (the shortest on a
    tie), or ``"none"``."""
    best, key = "none", (0.0, 0.0)
    for name, hs, he in trace.host:
        ov = min(e, he) - max(s, hs)
        if name != WINDOW_SPAN and ov > 0 and (ov, hs - he) > key:
            best, key = name, (ov, hs - he)
    return best


def reduce(trace: Trace, n_top: int = 10) -> dict:
    """busy_s (averaged over the device planes), window_s, idle share,
    the top device ops by summed time, and the longest idle gaps of the
    first device, each named by its covering host span."""
    if not trace.devices:
        raise ValueError("trace has no device plane")
    w0, w1 = window(trace)
    busy_per_device = []
    for evs in trace.devices.values():
        busy = union(_clip([(s, e) for _, s, e in evs], w0, w1))
        busy_per_device.append(busy)
    busy_s = sum(sum(e - s for s, e in b) for b in busy_per_device) \
        / len(busy_per_device) / 1e9
    window_s = (w1 - w0) / 1e9
    per_op: dict[str, float] = {}
    for evs in trace.devices.values():
        for name, s, e in evs:
            if e > w0 and s < w1:
                per_op[name] = per_op.get(name, 0.0) \
                    + (min(e, w1) - max(s, w0)) / 1e9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:n_top]
    first = busy_per_device[0]
    gaps, t = [], w0
    for s, e in first + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [[covering_span(trace, s, e), (e - s) / 1e9]
                 for s, e in gaps[:n_top]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": [[n, v] for n, v in top_ops],
            "idle_gaps": idle_gaps}
