"""Where a cell's device time goes, per graph node, and what the profiler
costs: one probe run of one cell, outside the benchmark's runs.

    python3 chipbench/scope_probe.py --workload <cell> --seed <n> \
        [--seconds 10] [--trace-seconds 2] [--host-tracer-level 1]

Builds and warms the cell's server as ``run.py`` does, reads the op map of
every bucket (``InferenceServer.op_scopes``), drives the traffic untraced
for ``--seconds`` (the flight-recorder readings), then ``--trace-seconds``
more under the profiler at the given host tracer level, and prints one
JSON line: device seconds per scope and the unattributed share, the
scopes of the first binary conv and their milliseconds per image, the
median device-busy milliseconds per traced batch between its dispatch and
its readback (through the ``obs.clock`` anchor) beside the untraced
``device_ms_p50``, and the traced tail's wall time and served rate beside
the untraced rate.  Like ``run.py`` it refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
# flight-recorder readers reported beside the trace's numbers
READERS = ("device_ms_p50", "preprocess_ms_p50", "sched_wait_ms_p95",
           "host_ms_per_image")


def probe(cell, seed: int, seconds: float, trace_seconds: float,
          host_tracer_level: int, log=print) -> dict:
    import jax

    from chipbench import costs, harness, scopes, stats, trace_reduce
    from repro.obs import trace as obs_trace

    config, traffic = cell.config, cell.traffic
    gen = harness.generator(traffic["kind"])
    inputs = gen.make_inputs(traffic, config, seed)
    wl, server = harness.build_server(config, traffic, seed)
    server.compile_buckets()
    gen.warm(server, inputs, traffic)
    server.flight.clear()
    t = time.perf_counter()
    op_maps: dict = {}
    for b in server.scheduler.buckets:
        op_maps.update(server.op_scopes(b))
    op_map_s = time.perf_counter() - t
    log(f"[probe] op maps of {len(op_maps)} modules: {op_map_s:.3f} s")

    layer_costs = costs.layer_costs(config["layers"], config["input_hw"])
    window = gen.drive(server, inputs, traffic, seconds)
    run = harness.Run(cell.name, seconds, cell.chips, 0.0, window["t0"],
                      window["t1"], window["requests"], server.flight.dump(),
                      layer_costs, None)
    untraced = {m: harness.reader(m)(run) for m in READERS}
    untraced["images_per_s"] = stats.images_in_window(run) / seconds

    server.flight.clear()
    tracer = obs_trace.install(obs_trace.Tracer(annotate_jax=True,
                                                max_events=1 << 22))
    opts = harness._profile_options()
    opts.host_tracer_level = host_tracer_level
    with tempfile.TemporaryDirectory(prefix="chipbench_probe_") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                tail = gen.drive(server, inputs, traffic, trace_seconds)
        finally:
            wall = time.perf_counter() - t
            jax.profiler.stop_trace()
            obs_trace.uninstall()
        st = scopes.load(trace_reduce.find_xplane(tdir))
    flight = server.flight.dump()
    served = [r for r in flight if r.get("outcome") == "served"]
    per_scope = scopes.scope_seconds(st, op_maps)
    total = sum(per_scope.values())
    first = scopes.first_conv_scopes(per_scope)
    at = scopes.anchor_ns(st)
    batch_ms = (scopes.batch_device_ms(st, flight, tracer.anchor_s, at)
                if at is not None and tracer.anchor_s is not None else [])
    top = sorted(per_scope.items(), key=lambda kv: -kv[1])
    log("[trace] top scopes " + json.dumps(
        [[k, round(v, 6)] for k, v in top[:8]]) + "; unattributed "
        f"{per_scope.get(scopes.NO_SCOPE, 0.0) / total:.4%}" if total else
        "[trace] no device op")
    return {
        "workload": cell.name, "seed": seed,
        "host_tracer_level": host_tracer_level, "op_map_s": op_map_s,
        "untraced": untraced,
        "traced": {
            "wall_s": wall, "served": len(served),
            "images_per_s": len(served) / wall,
            "device_op_s": total,
            "unattributed_share": (per_scope.get(scopes.NO_SCOPE, 0.0)
                                   / total if total else None),
            "scopes": dict(top),
            "first_conv_scopes": first,
            "first_conv_ms_per_image": (
                sum(per_scope[s] for s in first) / len(served) * 1e3
                if first and served else None),
            "anchor": at is not None,
            "host_spans": sum(1 for n, _, _ in st.host
                              if n.startswith("serve.")),
            "batch_device_ms_p50": stats.percentile(sorted(batch_ms), 0.5),
            "batches": len(batch_ms)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-seconds", type=float, default=2.0)
    ap.add_argument("--host-tracer-level", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, run

    run.use_checkout_caches(ROOT)
    cell = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("scope_probe: needs a TPU", file=sys.stderr)
        return 3
    out = probe(cell, args.seed, args.seconds, args.trace_seconds,
                args.host_tracer_level,
                log=lambda s: print(s, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
