"""Serving-path benchmark: sync vs async dispatch, single vs multi-device.

Measures end-to-end serving throughput and latency through the
:class:`~repro.serving.server.InferenceServer` — the whole subsystem
(scheduler assembly, bucket padding, executable-cache dispatch, result
scatter), not just the kernel — and writes the machine-readable
``BENCH_serving.json`` perf artifact:

* **sync vs async**: the synchronous drain loop (block on every batch)
  against async double-buffered dispatch (batch k+1 dispatched while
  batch k is in flight).  Same engine, same precompiled executables —
  the delta is purely the overlap of host-side batch assembly/scatter
  with device compute.
* **single vs sharded vs pipelined**: the same stream under both
  placements (DESIGN.md §13) — data-parallel batch sharding
  (``DataParallel``) and pipeline stages cut at HBM touch points
  (``Pipelined``).  These need >1 device, so they run in a SUBPROCESS
  on a forced 4-device host mesh
  (``--xla_force_host_platform_device_count``), together with their own
  single-device baseline so the speedup ratios are self-consistent.
  Forced host devices share the machine's cores: the rows verify the
  placement path end to end and calibrate its overhead; the ratios
  become real speedups only on genuinely parallel hardware.  The rows
  are marked ``skipped`` only when the subprocess cannot be spawned at
  all.

Networks are the paper's (YOLOv2-Tiny is fully convolutional, so it also
runs at reduced resolutions where serving overhead — not conv FLOPs —
dominates and the async win is largest).

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

import jax
import numpy as np

from benchmarks.common import emit, skipped, write_bench

MESH_DEVICES = 4

_MD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
# A forced host mesh is a CPU measurement: keep the child off any chip
# (which the parent may hold) and out of chip rows.
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, {src!r})
import json
import jax
import numpy as np
from repro.distributed import DataParallel, Pipelined
from repro.models import paper_nets
from repro.serving import InferenceServer, PhoneBitEngine, buckets_for

spec, (h, w, c), params = paper_nets.init({name!r})
if {input_hw!r}:
    h = w = {input_hw!r}
engine = PhoneBitEngine.from_trained(params, spec, (h, w),
                                     matmul_mode={matmul_mode!r})

def serve(placement):
    server = InferenceServer(engine, max_batch={max_batch},
                             max_wait_s=0.0,
                             buckets=buckets_for({max_batch}),
                             async_dispatch=True, placement=placement)
    server.compile_buckets()
    rng = np.random.default_rng(0)
    for _ in range({requests}):
        server.submit(rng.integers(0, 256, (h, w, c), dtype=np.uint8))
    server.drain()
    return server.metrics()

out = dict(
    baseline=serve(None),
    sharded=serve(DataParallel.over({n_dev})),
    pipelined=serve(Pipelined.over({n_dev})),
)
print("BENCHJSON:" + json.dumps(out))
"""


def _multi_device_rows(name: str, *, input_hw: int | None,
                       requests: int, max_batch: int,
                       matmul_mode: str, n_dev: int = MESH_DEVICES,
                       timeout: int = 900) -> dict:
    """Sharded + pipelined serving metrics, measured on a forced
    ``n_dev``-device host mesh in a subprocess (the placeholder-device
    flag must be set before jax imports and must not leak into this
    process).  Returns the three streams' metrics; ``skipped`` rows only
    when the subprocess itself cannot be spawned."""
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    script = _MD_SCRIPT.format(n_dev=n_dev, src=src, name=name,
                               input_hw=input_hw,
                               matmul_mode=matmul_mode,
                               max_batch=max_batch, requests=requests)
    try:
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True,
                           timeout=timeout)
    except OSError as e:          # spawn itself failed: report why
        return {k: skipped(f"subprocess spawn failed: {e}")
                for k in ("baseline", "sharded", "pipelined")}
    if r.returncode != 0:         # a real failure must fail the bench
        raise RuntimeError(
            f"multi-device bench subprocess failed for {name}:\n"
            f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}")
    payload = [l for l in r.stdout.splitlines()
               if l.startswith("BENCHJSON:")]
    if not payload:
        raise RuntimeError(f"multi-device bench emitted no payload for "
                           f"{name}:\n{r.stdout}")
    return json.loads(payload[-1][len("BENCHJSON:"):])


def _serve_stream(engine, hwc, *, requests: int, max_batch: int,
                  buckets: tuple[int, ...], async_dispatch: bool) -> dict:
    from repro.serving import InferenceServer

    server = InferenceServer(engine, max_batch=max_batch, max_wait_s=0.0,
                             buckets=buckets,
                             async_dispatch=async_dispatch)
    server.compile_buckets()
    rng = np.random.default_rng(0)
    for _ in range(requests):
        server.submit(rng.integers(0, 256, hwc, dtype=np.uint8))
    server.drain()
    return server.metrics()


def _best(runs: list[dict]) -> dict:
    return max(runs, key=lambda m: m["throughput"] or 0)


def _ratio(num: dict, den: dict):
    if num.get("throughput") and den.get("throughput"):
        return num["throughput"] / den["throughput"]
    return None


def bench_network(name: str, *, input_hw: int | None = None,
                  requests: int = 32, max_batch: int = 8,
                  matmul_mode: str = "xla", trials: int = 2) -> dict:
    from repro.models import paper_nets
    from repro.serving import PhoneBitEngine, buckets_for

    spec, (h, w, c), params = paper_nets.init(name)
    if input_hw:
        h = w = input_hw
    engine = PhoneBitEngine.from_trained(params, spec, (h, w),
                                         matmul_mode=matmul_mode)
    buckets = buckets_for(max_batch)
    kw = dict(requests=requests, max_batch=max_batch, buckets=buckets)
    # Paired measurement: alternate sync/async streams back-to-back and
    # take the MEDIAN of per-pair throughput ratios.  Machine drift on a
    # shared host moves both streams of a pair together and cancels in
    # the ratio, where a best-of comparison across minutes would be
    # dominated by it; per-mode metrics still report each mode's best
    # stream.
    sync_runs, async_runs, ratios = [], [], []
    for _ in range(trials):
        s = _serve_stream(engine, (h, w, c), async_dispatch=False, **kw)
        a = _serve_stream(engine, (h, w, c), async_dispatch=True, **kw)
        sync_runs.append(s)
        async_runs.append(a)
        if s["throughput"] and a["throughput"]:
            ratios.append(a["throughput"] / s["throughput"])
    sync, async_ = _best(sync_runs), _best(async_runs)
    paired = sorted(ratios)[len(ratios) // 2] if ratios else None

    # Placement rows on the forced 4-device mesh; speedups are vs the
    # SAME subprocess's single-device baseline (self-consistent ratios —
    # the parent's async stream ran under a different device config).
    md = _multi_device_rows(name, input_hw=input_hw, requests=requests,
                            max_batch=max_batch,
                            matmul_mode=matmul_mode)
    row = {
        "network": name, "input_hw": h, "requests": requests,
        "max_batch": max_batch, "buckets": list(buckets),
        "matmul_mode": matmul_mode,
        "sync": sync, "async": async_,
        "sharded": md["sharded"], "pipelined": md["pipelined"],
        "multi_device": {
            "n_devices": MESH_DEVICES,
            "forced_host_mesh": True,
            "baseline": md["baseline"],
        },
        # median of paired ratios — the drift-robust speedup estimate
        "async_speedup": paired,
        "async_speedup_pairs": [round(r, 4) for r in ratios],
        "shard_speedup": _ratio(md["sharded"], md["baseline"]),
        "pipeline_speedup": _ratio(md["pipelined"], md["baseline"]),
    }
    return row


def run(smoke: bool = False, out: str = "BENCH_serving.json") -> dict:
    # Double-buffering pays in the overhead-dominated regime — small
    # per-dispatch device work (single-image buckets, reduced resolution)
    # where per-request host staging/dispatch/readback is comparable to
    # compute and async hides it behind the in-flight batch.  At
    # compute-saturated CPU shapes the device *is* the host (XLA's
    # threads and the serving loop share cores), so overlap buys nothing
    # there — that row is reported anyway; the TPU/serving-shard regime
    # is the small-per-device-work one.
    if smoke:
        # CI tripwire: the fully-conv paper net, latency-serving shape.
        cases = [dict(name="yolov2-tiny", input_hw=32, requests=64,
                      max_batch=1, trials=5)]
    else:
        cases = [
            dict(name="yolov2-tiny", input_hw=None, requests=16,
                 max_batch=4),
            dict(name="yolov2-tiny", input_hw=32, requests=96,
                 max_batch=1, trials=9),
            dict(name="alexnet", input_hw=None, requests=16, max_batch=4),
        ]
    rows = [bench_network(c.pop("name"), **c) for c in cases]

    csv_rows = [{
        "network": r["network"], "hw": r["input_hw"],
        "sync_img_s": r["sync"]["throughput"],
        "async_img_s": r["async"]["throughput"],
        "async_speedup": r["async_speedup"],
        "async_p50_ms": r["async"]["p50_ms"],
        "async_p95_ms": r["async"]["p95_ms"],
        "shard_img_s": r["sharded"].get("throughput", ""),
        "pipeline_img_s": r["pipelined"].get("throughput", ""),
    } for r in rows]
    emit(csv_rows, "§Serving: sync vs async vs sharded vs pipelined")

    report = {
        "device": f"{jax.default_backend()}:"
                  f"{jax.devices()[0].device_kind}",
        "n_devices": len(jax.devices()),
        "mesh_devices": MESH_DEVICES,
        "smoke": smoke,
        "nets": rows,
        "summary": {
            "n_nets": len(rows),
            "async_wins": sum(1 for r in rows
                              if (r["async_speedup"] or 0) > 1.0),
            "best_async_speedup": max((r["async_speedup"] or 0)
                                      for r in rows),
            "sharded_measured": sum(
                1 for r in rows if r["sharded"].get("throughput")),
            "pipelined_measured": sum(
                1 for r in rows if r["pipelined"].get("throughput")),
        },
    }
    report = write_bench(out, report)
    print(f"wrote {out} (async wins "
          f"{report['summary']['async_wins']}/{len(rows)}, best speedup "
          f"{report['summary']['best_async_speedup']:.2f}x, "
          f"placement rows measured "
          f"{report['summary']['sharded_measured']}+"
          f"{report['summary']['pipelined_measured']}/{2 * len(rows)})")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmarks.serving_bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized single case; still writes "
                         "BENCH_serving.json")
    args = ap.parse_args(argv)
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
