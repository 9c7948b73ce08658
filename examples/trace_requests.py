"""Tracing a serving burst: spans, flight stages, node scopes, Chrome export.

    PYTHONPATH=src python examples/trace_requests.py

The observability layer (DESIGN.md §10) instruments the production serve
path without touching what it computes: spans are host-side scopes around
the blocking boundaries, flight records are stamped on the server clock,
and the graph nodes are named scopes inside the served executable — so
tracing changes no jit trace and no served bit.  This example shows all
three on a YOLOv2-Tiny burst (resolution reduced from the paper's 416 —
the net is fully convolutional, so only the grid changes):

1. **Serve-path spans** — submit → assemble → stage (with one
   ``serve.preprocess`` per row under a hook) → dispatch → device →
   scatter for every batch, with the per-bucket compile spans from
   ``compile_buckets()``.  Results stay bit-exact vs the flat-path oracle
   (``cross_check``) and ``engine.trace_count`` stays where
   precompilation left it.
2. **Flight stages** — every served request's record carries
   ``arrival_s ≤ assembled_s ≤ dispatched_s ≤ ready_s ≤ done_s`` and the
   hook's own ``preprocess_s``: how long it queued, staged, and waited
   for the device.
3. **Node scopes** — ``server.op_scopes(bucket)`` maps every device op of
   the served executable to its graph node (``n<id>.<op>``) or fused
   chain region (``region.<ids>``): the map a profiler trace's ops are
   charged through.  On a ``vpu_chain`` engine the fused conv runs are
   single ``region.*`` scopes.

The export is Chrome trace-event JSON — load it at ``chrome://tracing``
or https://ui.perfetto.dev — and ``validate_trace`` is the same schema
check CI's obs-smoke job runs.
"""

from collections import Counter

import numpy as np

from repro.models import paper_nets
from repro.obs import trace
from repro.serving import InferenceServer, PhoneBitEngine

HW = 32      # reduced from 416 for the CPU demo
OUT = "trace_requests.json"


def center_crop(img: np.ndarray) -> np.ndarray:
    """The preprocess hook: a raw frame's central HW x HW window."""
    h, w = img.shape[:2]
    top, left = (h - HW) // 2, (w - HW) // 2
    return np.ascontiguousarray(img[top:top + HW, left:left + HW])


spec, (h, w, c), params = paper_nets.init("yolov2-tiny")
engine = PhoneBitEngine.from_trained(params, spec, (HW, HW),
                                     matmul_mode="xla_pm1")
server = InferenceServer(engine, max_batch=4, max_wait_s=0.0,
                         buckets=(1, 2, 4), preprocess=center_crop)

tracer = trace.install()                    # tracing ON from here

# ---- Part 1: serve a burst under tracing ---------------------------------
server.compile_buckets()                    # compile.bucket spans
t0 = engine.trace_count
rng = np.random.default_rng(0)
frames = [rng.integers(0, 256, (HW + 8, HW + 8, 3), dtype=np.uint8)
          for _ in range(8)]
reqs = [server.submit(f) for f in frames]
server.drain()

assert all(r.done for r in reqs)
assert engine.trace_count == t0, "tracing must never retrace"
# Tracing changes no served bit: the graph path still matches the flat
# packed_forward oracle on a full bucket.
ref = np.asarray(engine.cross_check(np.stack([center_crop(f)
                                              for f in frames[:4]])))
for r, row in zip(reqs[:4], ref):
    np.testing.assert_array_equal(np.asarray(r.result), row)
m = server.metrics()
print(f"[serve] {m['served']} served, p50 {m['p50_ms']:.1f} ms")

# ---- Part 2: each request's stages from its flight record ----------------
served = [r for r in server.flight.dump() if r["outcome"] == "served"]
for r in served:
    assert (r["arrival_s"] <= r["assembled_s"] <= r["dispatched_s"]
            <= r["ready_s"] <= r["done_s"]), r
    assert 0.0 < r["preprocess_s"] <= r["stage_s"]
for r in served[:3]:
    wait = r["assembled_s"] - r["arrival_s"]
    device = r["ready_s"] - r["dispatched_s"]
    print(f"  req {r['id']}: wait {1e3 * wait:.2f} ms, preprocess "
          f"{1e3 * r['preprocess_s']:.2f} ms, stage "
          f"{1e3 * r['stage_s']:.2f} ms, device {1e3 * device:.2f} ms")

# ---- Part 3: node and region scopes of the served executable -------------
chain_engine = PhoneBitEngine.from_trained(params, spec, (HW, HW),
                                           matmul_mode="vpu_chain")
chain_server = InferenceServer(chain_engine, buckets=(1,), max_batch=1)
chain_server.compile_buckets()
t1 = chain_engine.trace_count
(ops,) = chain_server.op_scopes(1).values()  # one module: the forward
per_scope = Counter(ops.values())
assert any(s.startswith("region.") for s in per_scope), per_scope
assert chain_engine.trace_count == t1, "building the map must not retrace"
x = center_crop(frames[0])
got = chain_server.submit(x)
chain_server.drain()
np.testing.assert_array_equal(np.asarray(got.result),
                              np.asarray(engine(x[None]))[0])
print("[scopes] HLO instructions per scope: "
      + ", ".join(f"{s} {n}" for s, n in sorted(per_scope.items())))

# ---- export + validate ---------------------------------------------------
trace.uninstall()                           # tracing OFF again
doc = tracer.export(OUT)
complete = trace.validate_trace(doc)        # schema + nesting check

names = {e["name"] for e in doc["traceEvents"]}
assert {"serve.assemble", "serve.stage", "serve.preprocess",
        "serve.dispatch", "serve.device", "serve.scatter",
        "compile.bucket"} <= names, names

by_cat: dict = {}
for e in complete:
    by_cat[e["cat"]] = by_cat.get(e["cat"], 0) + 1
print(f"[trace] {len(doc['traceEvents'])} events "
      f"({len(complete)} spans) -> {OUT}; by kind: {by_cat}")
print("OK — open the file at chrome://tracing or ui.perfetto.dev")
