"""One run of one cell: build the served program from the seed, warm up
the cell's own shapes, measure for a fixed window, read the metrics, and
check every served answer against the plain reference.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name:

* ``configs/<config>.json``: the model, its mode, its layers (for
  ``costs.py`` and ``reference.py``) and the limits of its comparison;
* ``traffic/<mix>.json``: a mix, naming its generator ``kind``, its
  parameters and the server's batch ``buckets``; ``traffic/<kind>.py`` is
  the generator;
* ``metrics/<metric>.py`` (or ``metrics/<base>.py`` for a metric named
  ``<base>.<variant>``): a reader ``read(run) -> float | None`` of one
  metric from the run record.  ``None`` means nothing to read, and the
  metric is left out of the result.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time
from typing import Any

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# seconds a traced run drives the traffic under the profiler, after its
# untraced window
TRACE_SECONDS = 2.0


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str):
    path = HERE / "traffic" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic generator {path}")
    return _load_module(path)


def reader(metric: str):
    """The ``read`` function of a metric: ``metrics/<name>.py``, else
    ``metrics/<base>.py`` for ``<base>.<variant>``."""
    for stem in (metric, metric.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load_module(path).read
    raise FileNotFoundError(f"no reader for metric {metric!r} under "
                            f"{HERE / 'metrics'}")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name, config, traffic, int(w["chips"]),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


class CompileCounter:
    """Counts JAX traces and compile requests while ``active``."""

    def __init__(self):
        import jax.monitoring as mon

        self.active = False
        self.traces = self.compiles = 0
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, name: str, _secs: float, **_kw) -> None:
        if not self.active:
            return
        if name.endswith("jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("backend_compile_duration"):
            self.compiles += 1


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: str
    seconds: float
    chips: int
    setup_s: float
    t0: float                 # window start and end, harness clock
    t1: float
    requests: list[dict]      # input, due, sent, done, outcome, result
    flight: list[dict]        # the server's flight records of the window
    costs: list               # costs.LayerCost per layer, per image
    peak: dict | None         # peaks.json row of the device kind
    trace: dict | None = None  # trace_reduce.reduce() of a traced run


def build_server(config: dict, traffic: dict, seed: int):
    """The served program exactly as a deployment builds it: the
    registered workload at the configuration's mode and seed, behind an
    ``InferenceServer`` with the cell's buckets and preprocess hook."""
    from repro import workloads

    wl = workloads.get(config["workload"], variant=config["variant"],
                       matmul_mode=config["matmul_mode"], seed=seed,
                       input_hw=tuple(config["input_hw"]))
    buckets = tuple(traffic["buckets"])
    server = wl.server(
        max_batch=max(buckets), max_wait_s=0.0, buckets=buckets,
        preprocess=(wl.preprocess_hook if traffic["preprocess"] else None),
        flight_capacity=1 << 21)
    return wl, server


def backend_report(wl) -> dict:
    """Per bucket, the backend (and tile) each node or chain serves on —
    the autotune and chain-sweep winners of this checkout."""
    out = {}
    for key, exe in sorted(wl.engine.engine._compiled.items(),
                           key=lambda kv: str(kv[0])):
        out[str(key[0])] = [
            f"{r['node']}:{r['op']}:{r['backend']}"
            + (f":{json.dumps(r['tile'], sort_keys=True)}" if r["tile"]
               else "")
            for r in exe.backend_report()]
    return out


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    # device events of XLA ops only: the reduction reads nothing else
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    return opts


def _drive_traced(gen, server, inputs: dict, traffic: dict,
                  trace_dir: str) -> dict:
    """The cell's traffic for ``TRACE_SECONDS`` more under the profiler,
    with the server's host spans written into the trace."""
    import jax

    from chipbench import trace_reduce
    from repro.obs import trace as obs_trace

    obs_trace.install(obs_trace.Tracer(annotate_jax=True,
                                       max_events=1 << 22))
    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            return gen.drive(server, inputs, traffic, TRACE_SECONDS)
    finally:
        jax.profiler.stop_trace()
        obs_trace.uninstall()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=print) -> dict:
    """Build, warm up, measure, read, check.  Returns the result line
    (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
    ``breakdown`` when traced, ``checks`` last).

    The window runs untraced in every run.  A traced run then drives the
    traffic for ``TRACE_SECONDS`` more under the profiler, which slows the
    host several-fold: metrics whose source is ``device_trace`` read that
    traced tail, every other metric reads the untraced window."""
    import jax

    from chipbench import compare, costs, trace_reduce

    devices = jax.devices()[:cell.chips]
    config, traffic = cell.config, cell.traffic
    gen = generator(traffic["kind"])
    counter = CompileCounter()

    t = time.perf_counter()
    inputs = gen.make_inputs(traffic, config, seed)
    wl, server = build_server(config, traffic, seed)
    log(f"[setup] inputs and program built: "
        f"{time.perf_counter() - t:.3f} s")
    for b, s in server.compile_buckets().items():
        log(f"[setup] compile bucket {b}: {s:.3f} s")
    t = time.perf_counter()
    gen.warm(server, inputs, traffic)
    server.flight.clear()
    log(f"[setup] warm-up through the server: "
        f"{time.perf_counter() - t:.3f} s")
    log(f"[setup] backends: {json.dumps(backend_report(wl))}")
    traces_before = wl.engine.trace_count

    tail = reduced = None
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tdir:
        counter.active = True
        setup_s = time.perf_counter() - t_start
        try:
            window = gen.drive(server, inputs, traffic, seconds)
            flight = server.flight.dump()
            if trace:
                server.flight.clear()
                tail = _drive_traced(gen, server, inputs, traffic, tdir)
        finally:
            counter.active = False
        if trace:
            path = trace_reduce.find_xplane(tdir)
            reduced = trace_reduce.reduce(trace_reduce.load(path))
            log(f"[trace] {os.path.getsize(path)} bytes: busy "
                f"{reduced['busy_s']:.6f} s of {reduced['window_s']:.6f} s")
    log(f"[window] traces {counter.traces}, backend compiles "
        f"{counter.compiles}, engine retraces "
        f"{wl.engine.trace_count - traces_before} inside the window"
        + (" and the traced tail" if trace else ""))
    served_rows = [r for r in flight if r.get("outcome") == "served"]
    batches = {(r["dispatched_s"], r["bucket"]) for r in served_rows}
    if batches:
        log(f"[window] {len(batches)} batches, mean real rows per batch "
            f"{len(served_rows) / len(batches):.3f}")
    mem = memory_peak_bytes(devices)

    kind = devices[0].device_kind
    peak = costs.peaks(kind) if devices[0].platform == "tpu" else None
    layer_costs = costs.layer_costs(config["layers"], config["input_hw"])
    run = Run(cell.name, seconds, cell.chips, setup_s, window["t0"],
              window["t1"], window["requests"], flight, layer_costs, peak)
    traced = None
    if trace:
        traced = Run(cell.name, TRACE_SECONDS, cell.chips, setup_s,
                     tail["t0"], tail["t1"], tail["requests"],
                     server.flight.dump(), layer_costs, peak, reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(
            traced if m["source"] == "device_trace" else run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the window is closed and the peak read: free the program, then
    # check what it served against the reference
    requests = run.requests + (traced.requests if trace else [])
    served = [r for r in requests if r.get("outcome") == "served"]
    del wl, server
    gc.collect()
    t = time.perf_counter()
    numbers = check_answers(config, traffic, seed, inputs, served, log)
    log(f"[check] reference and comparison: {time.perf_counter() - t:.3f} s "
        f"over {len(served)} served answers")
    ok, checks = compare.verdict(numbers, config["limits"])
    ok = ok and bool(served)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    attempted = len(requests)
    line = {"correct": ok, "attempted": attempted,
            "failed": attempted - len(served), "metrics": metrics,
            "device": {"platform": devices[0].platform, "kind": kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": mem}}
    if trace:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checks
    return line


def check_answers(config: dict, traffic: dict, seed: int, inputs: dict,
                  served: list[dict], log=lambda s: None) -> dict:
    """The numbers compared: every distinct served answer against the
    float32 reference's output for its input, the largest over all of
    them.  The reference's float32 ties are decided as the served answers
    read closest (``compare.resolve_ties``): one decision per tie, the
    same for every input."""
    from chipbench import compare, reference

    if not served:
        return {}
    ref = reference.Reference(config, seed)
    used = sorted({r["input"] for r in served})
    x = network_inputs(ref, traffic, inputs, used)
    answers: dict[int, dict[bytes, np.ndarray]] = {i: {} for i in used}
    for r in served:
        res = np.asarray(r["result"])
        answers[r["input"]].setdefault(res.tobytes(), res)
    rows = [np.stack(list(answers[i].values())) for i in used]

    def judge(k: int, raw: np.ndarray) -> dict:
        if config["task"] == "classify":
            return compare.compare_classify(rows[k], raw[None])
        frame = compare.FrameReference(raw, config["head"],
                                       config["input_hw"])
        return compare.compare_detect(rows[k], [frame],
                                      np.zeros(len(rows[k]), np.int64))

    def rejudge(flips, idx):
        raw, hit = ref.run(x[idx], flips)
        return [judge(k, r) for k, r in zip(idx, raw)], hit

    raw, hits = ref.run(x)
    per = [judge(k, r) for k, r in enumerate(raw)]
    flips, per, hits = compare.resolve_ties(per, hits, rejudge,
                                            config["limits"])
    log(f"[check] float32 ties: {len(ref.ties)} in the weights, "
        f"{int(hits.any(axis=0).sum())} hit by the inputs, flipped "
        + json.dumps([ref.ties[t][:3] for t in sorted(flips)]))
    return {k: max(n[k] for n in per) for k in per[0]}


def network_inputs(ref, traffic: dict, inputs: dict,
                   used: list[int]) -> np.ndarray:
    """The network-size inputs of the input indices in ``used``,
    preprocessed by the reference where the traffic sends raw images."""
    payloads = inputs["payloads"]
    return np.stack([ref.preprocess(payloads[i]) if traffic["preprocess"]
                     else payloads[i] for i in used])
