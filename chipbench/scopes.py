"""Device time per graph node, and the program's clock on the device
timeline, from a profiler trace (``.xplane.pb``).

The served program traces each graph node under a named scope
(``n<id>.<op>``), each fused chain region under ``region.<ids>`` and the
postprocess head under ``head``, and ``InferenceServer.op_scopes(bucket)``
maps every optimized HLO instruction of a bucket's modules to its scope:
``{module: {instruction: scope}}``.  A trace names device ops by
instruction inside ``XLA Modules`` events (``jit__run(<fingerprint>)``),
so :func:`scope_seconds` charges each ``XLA Ops`` event to a scope through
the map of the module it ran in; an op the map does not know reads
``"none"``.  Instruction names repeat across modules, not within one.

``Tracer(annotate_jax=True)`` writes one ``obs.clock`` annotation and keeps
its own clock reading inside it (``Tracer.anchor_s``); :func:`to_trace_ns`
maps a program time (flight stamps, spans) onto the trace through it.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from chipbench import trace_reduce

MODULES_LINE = "XLA Modules"
ANCHOR = "obs.clock"
NO_SCOPE = "none"
_NODE = re.compile(r"n(\d+)\.(\w+)")
_REGION = re.compile(r"region\.(\d+(?:\+\d+)*)")
# ops of a first binary conv, by the executor's graph op names
CONV_OPS = ("packed_conv", "packed_conv_pool", "conv_counts")


@dataclasses.dataclass
class ScopedTrace:
    """Device ops as (module, instruction, start_ns, end_ns), on the
    trace's one clock, and the host annotations the reductions read."""
    ops: list[tuple[str, str, float, float]]
    host: list[tuple[str, float, float]]


def _module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def load(path: str, host_prefixes=trace_reduce.HOST_PREFIXES + (ANCHOR,)
         ) -> ScopedTrace:
    """Every ``XLA Ops`` event of every device plane with the module it
    ran in (the ``XLA Modules`` event that holds its start), and the host
    events whose names start with ``host_prefixes``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: list = []
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.end_ns, _module_name(e.name))
                          for e in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            for e in (lines[trace_reduce.OPS_LINE].events
                      if trace_reduce.OPS_LINE in lines else ()):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] \
                    else NO_SCOPE
                name = e.name.split(" = ", 1)[0].lstrip("%")
                ops.append((mod, name, e.start_ns, e.end_ns))
        else:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(host_prefixes))
    return ScopedTrace(ops, host)


def window(trace: ScopedTrace) -> tuple[float, float]:
    """The traced window span, else the extent of the device ops."""
    return trace_reduce.window(trace_reduce.Trace(
        {"": [(name, s, e) for _, name, s, e in trace.ops]}, trace.host))


def scope_seconds(trace: ScopedTrace, op_maps: dict[str, dict[str, str]]
                  ) -> dict[str, float]:
    """Seconds of device ops per scope inside the window, summed like
    ``trace_reduce.reduce``'s ``device_ops``: each op clipped to the
    window, overlapping ops each counted."""
    w0, w1 = window(trace)
    out: dict[str, float] = {}
    for mod, name, s, e in trace.ops:
        if e > w0 and s < w1:
            scope = op_maps.get(mod, {}).get(name, NO_SCOPE)
            out[scope] = out.get(scope, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
    return out


def _scope_order(scope: str) -> tuple[int, str]:
    """The first graph node id a scope holds (regions: their head), for
    schedule order; unknown scopes sort last."""
    m = _NODE.fullmatch(scope) or _REGION.fullmatch(scope)
    return (int(m.group(1).split("+")[0]) if m else 1 << 30, scope)


def first_conv_scopes(scopes) -> list[str]:
    """The scopes up to and including the one that holds the first binary
    conv, in schedule order: the bit-plane expansion that feeds it and
    the conv's node or region, with their pads, copies and layout
    changes.  Empty when no scope holds a conv."""
    out = []
    for sc in sorted(set(scopes) - {NO_SCOPE, "head"}, key=_scope_order):
        out.append(sc)
        node = _NODE.fullmatch(sc)
        if _REGION.fullmatch(sc) or (node and node.group(2) in CONV_OPS):
            return out
    return []


def anchor_ns(trace: ScopedTrace) -> float | None:
    """Midpoint of the ``obs.clock`` annotation (the program clock was
    read inside it), or None when the trace has none."""
    for name, s, e in trace.host:
        if name == ANCHOR:
            return (s + e) / 2.0
    return None


def to_trace_ns(t: float, anchor_s: float, anchor_at_ns: float) -> float:
    """A program clock reading ``t`` (seconds) on the trace's clock."""
    return anchor_at_ns + (t - anchor_s) * 1e9


def batch_device_ms(trace: ScopedTrace, flight: list[dict],
                    anchor_s: float, anchor_at_ns: float) -> list[float]:
    """Per served batch, milliseconds in which the device ran ops between
    the batch's ``dispatched_s`` and ``ready_s`` (union of op intervals),
    both mapped onto the trace through the anchor."""
    busy = trace_reduce.union((s, e) for _, _, s, e in trace.ops)
    starts = [s for s, _ in busy]
    spans = {(r["dispatched_s"], r["ready_s"])
             for r in flight if r.get("outcome") == "served"
             and "ready_s" in r}
    out = []
    for d, r in sorted(spans):
        a = to_trace_ns(d, anchor_s, anchor_at_ns)
        b = to_trace_ns(r, anchor_s, anchor_at_ns)
        k = max(bisect.bisect_right(starts, a) - 1, 0)
        ns = 0.0
        for s, e in busy[k:]:
            if s >= b:
                break
            ns += max(0.0, min(e, b) - max(s, a))
        out.append(ns / 1e6)
    return out
