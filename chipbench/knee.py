"""Sweep an open-loop cell's load on the chip to find its knee: the
highest offered rate at which the p95 latency stays within a limit and the
backlog does not grow.

    python3 chipbench/knee.py --workload yolo416_camera --streams 1,2,3,4 \
        --seeds 11,12,13 --seconds 15 --limit-ms 100

One process builds and warms the cell's server once (weights from the
first seed), then drives its traffic at each stream count in turn, once
for each seed's frames and phases.  The backlog grows when the median
latency of the last fifth of the requests exceeds twice that of the first
fifth.  Prints one JSON line per load.  The benchmark's own runs never
sweep: the cell's traffic file holds the stream count chosen from this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--limit-ms", type=float, default=100.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, run, stats

    run.use_checkout_caches(ROOT)
    cell = harness.load_cell(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("chipbench knee: needs a TPU", file=sys.stderr)
        return 3
    from repro.cache import configure_compile_cache

    configure_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    gen = harness.generator(cell.traffic["kind"])
    wl, server = harness.build_server(cell.config, cell.traffic, seeds[0])
    server.compile_buckets()
    top = max(int(s) for s in args.streams.split(","))
    gen.warm(server, gen.make_inputs(dict(cell.traffic, streams=top),
                                     cell.config, seeds[0]), cell.traffic)
    for n, seed in ((int(s), seed) for s in args.streams.split(",")
                    for seed in seeds):
        params = dict(cell.traffic, streams=n)
        inputs = gen.make_inputs(params, cell.config, seed)
        server.flight.clear()
        t = time.perf_counter()
        window = gen.drive(server, inputs, params, args.seconds)
        wall = time.perf_counter() - t
        r = harness.Run(cell.name, args.seconds, cell.chips, 0.0,
                        window["t0"], window["t1"], window["requests"],
                        server.flight.dump(), [], None)
        lat = [x["done"] - x["due"] for x in r.requests
               if x.get("outcome") == "served"]
        fifth = max(1, len(lat) // 5)
        growth = (statistics.median(lat[-fifth:])
                  / statistics.median(lat[:fifth])) if lat else None
        p95 = stats.percentile(stats.latencies_s(r), 0.95)
        print(json.dumps({
            "streams": n, "seed": seed,
            "offered_per_s": n * params["fps"],
            "served_per_s": stats.images_in_window(r) / args.seconds,
            "latency_p50_ms": stats.percentile(stats.latencies_s(r), 0.5)
            * 1e3, "latency_p95_ms": p95 * 1e3,
            "gen_late_ms_p95": stats.percentile(
                sorted(x["sent"] - x["due"] for x in r.requests), 0.95) * 1e3,
            "backlog_growth": growth, "drain_s": wall - args.seconds,
            "within_limit": bool(p95 * 1e3 <= args.limit_ms
                                 and (growth or 0) <= 2.0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
