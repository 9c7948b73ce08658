"""Chip smoke run: serve the packed path on a TPU and check every answer.

    python3 chip_smoke.py             # one chip: the phases below
    python3 chip_smoke.py --chips 4   # four chips: only the paths across them

One process drives everything (a chip belongs to one process).  The run
fails, and prints no result, when JAX finds no TPU.

One chip:

* **YOLOv2-Tiny @ 416 (paper widths) under ``vpu_chain``** — 8 raw uint8
  frames of mixed sizes go through ``workloads.get(...)`` → the
  workload's letterbox hook → ``InferenceServer`` (buckets 1 and 4).
  The same frames served under ``xla`` on the same chip are the
  reference: decoded boxes must match as ``tests/harness.py`` requires
  (survivor mask and classes exact, values within 1e-4; survivors per
  frame are printed), and the bucket executables the server ran (with
  their autotuned chain tiles) must give the ``xla`` buckets' packed
  tail (last packed layer's words) bit for bit and their logits within
  1e-4.  One frame's ``xla`` logits are checked against the float oracle
  ``bnn_model.float_forward``.
* **AlexNet @ 227 (paper widths)**, one batch of 4 under each per-node
  Pallas backend (``vpu_popcount``, ``mxu_pm1``, ``vpu_direct``,
  ``vpu_direct_pool``): packed tail bit-exact with ``xla``, logits
  within 1e-4.

Nothing may pass by falling back.  Every phase asserts: each request
resolved ``served`` with the requested mode in its flight record; zero
retries, errors, rejections and demotions; every conv node of the
bucket executable on the requested kernel (under ``vpu_chain``: inside
a chain) — ``vpu_direct`` for a pool-free conv under ``vpu_direct_pool``
is the same kernel without the pool stage, anything else is a
degradation; and ``tpu_custom_call`` in the compiled executable, which
rules out interpret mode.

Four chips (``--chips 4``): four one-chip ``ReplicaGroup`` lanes serving
YOLOv2-Tiny @ 416 under ``vpu_chain``, bit-exact with one lane, each
lane's output on its own device; and the ``launch.serve --shard``
data-parallel bucket against the unsharded one.

Earlier lines report compile seconds per bucket and the device; the last
line is the JSON verdict ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
SEED = 0
FRAME_SIZES = [(480, 640), (416, 416), (300, 500), (720, 1280),
               (375, 500), (416, 300), (240, 320), (512, 512)]
PER_NODE_MODES = ("vpu_popcount", "mxu_pm1", "vpu_direct",
                  "vpu_direct_pool")
CONV_OPS = ("packed_conv", "packed_conv_pool")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def frames(sizes, seed: int = SEED) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            for h, w in sizes]


def serve(wl, imgs, buckets, groups, tag: str, **server_kw):
    """Serve ``imgs`` in submit groups (drain after each); returns the
    server and the requests in submit order."""
    server = wl.server(max_batch=max(buckets), max_wait_s=0.0,
                       buckets=buckets, **server_kw)
    for b, s in server.compile_buckets().items():
        print(f"[{tag}] compile bucket {b}: {s:.1f} s", flush=True)
    reqs, i = [], 0
    for g in groups:
        reqs += [server.submit(im) for im in imgs[i:i + g]]
        server.drain()
        i += g
    check(i == len(imgs), f"{tag}: groups cover {i} of {len(imgs)} frames")
    return server, reqs


def check_served(server, reqs, mode: str, tag: str) -> None:
    """Every request served at the requested mode; no resilience event."""
    for r in reqs:
        check(r.outcome == "served",
              f"{tag}: request {r.id} resolved {r.outcome!r} ({r.error})")
    served = [rec for rec in server.flight.dump()
              if rec.get("outcome") == "served"]
    check(len(served) >= len(reqs), f"{tag}: {len(served)} flight records "
          f"for {len(reqs)} requests")
    for rec in served:
        check(rec.get("mode") == mode,
              f"{tag}: flight record mode {rec.get('mode')!r} != {mode!r}")
    m = server.metrics()
    for key in ("retries", "errors", "rejected", "degraded"):
        check(not m.get(key), f"{tag}: metrics {key}={m.get(key)}")
    check(m.get("mode") == mode, f"{tag}: server mode {m.get('mode')!r}")


def expected_backend(op: str, mode: str) -> str:
    if mode == "vpu_direct_pool" and op == "packed_conv":
        return "vpu_direct"      # no pool to fuse: the same kernel
    return mode


def check_executor(exe, x_shape, mode: str, tag: str) -> None:
    """Backend report (no conv node degraded) + Pallas kernels in the
    compiled bucket executable."""
    import jax
    import jax.numpy as jnp

    report = exe.backend_report()
    for row in report:
        print(f"[{tag}] node {row['node']} {row['op']}: {row['backend']}"
              f"{' ' + str(row['tile']) if row['tile'] else ''}")
        if row["op"] in CONV_OPS:
            want = expected_backend(row["op"], mode)
            check(mode != "vpu_chain" and row["backend"] == want,
                  f"{tag}: conv node {row['node']} on {row['backend']}, "
                  f"not {'a chain' if mode == 'vpu_chain' else want}")
    if mode == "vpu_chain":
        check(any(r["op"] == "chain" for r in report),
              f"{tag}: no chain formed")
    text = exe._jitted.lower(
        exe.arrays, jax.ShapeDtypeStruct(x_shape, jnp.uint8)
    ).compile().as_text()
    check("tpu_custom_call" in text,
          f"{tag}: no tpu_custom_call in the compiled executable")


def packed_tail(exe, x) -> np.ndarray:
    """Run a bucket executable's own program — its graph, per-node
    backends and tiles, and chains with their tuned tiles — up to its
    last packed node: the packed words the float head reads."""
    import jax.numpy as jnp
    from repro.runtime import GraphExecutor
    from repro.runtime.graph import PACKED_OPS

    g = exe.graph.copy()
    g.output_id = [n for n in g.topo_order()
                   if g.nodes[n].op in PACKED_OPS][-1]
    keep = set()
    stack = [g.output_id]
    while stack:
        n = stack.pop()
        if n not in keep:
            keep.add(n)
            stack.extend(g.nodes[n].inputs)
    g.nodes = {n: node for n, node in g.nodes.items() if n in keep}
    regions = [c for c in exe.regions if c.tail in keep]
    check(all(set(c.node_ids) <= keep for c in regions),
          "the last packed node sits inside a chain, not at its tail")
    sub = GraphExecutor(
        g, {n: b for n, b in exe.backends.items() if n in keep},
        {n: t for n, t in exe.tile_configs.items() if n in keep},
        regions=regions)
    return np.asarray(sub(jnp.asarray(x)))


def compare_bucket(exe, ref_exe, x, tag: str) -> tuple[int, float]:
    """The served bucket executable against the ``xla`` one on the same
    frames: packed tail bit-exact, logits within 1e-4.  Returns (tail
    words, logits max |diff|)."""
    import jax.numpy as jnp

    tail, ref_tail = packed_tail(exe, x), packed_tail(ref_exe, x)
    check(tail.shape == ref_tail.shape,
          f"{tag}: packed tail shapes {tail.shape} {ref_tail.shape}")
    check(np.array_equal(tail, ref_tail), f"{tag}: packed tail differs from xla in "
          f"{int(np.sum(tail != ref_tail))} words")
    # the executables donate their input: a fresh device copy per call
    logits = np.asarray(exe(jnp.asarray(x)))
    ref = np.asarray(ref_exe(jnp.asarray(x)))
    check(logits.shape == ref.shape, f"{tag}: logits shapes {logits.shape} "
          f"{ref.shape}")
    err = float(np.max(np.abs(logits - ref)))
    check(err <= 1e-4, f"{tag}: logits differ from xla by {err}")
    return tail.size, err


def compare_decoded(got, want, task: str, tag: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape, f"{tag}: shapes {got.shape} {want.shape}")
    if task == "classify":
        check(np.array_equal(got[..., 0], want[..., 0]),
              f"{tag}: top-k classes differ")
    else:
        check(np.array_equal(got[..., 4] > 0, want[..., 4] > 0),
              f"{tag}: surviving detections differ")
        check(np.array_equal(got[..., 5], want[..., 5]),
              f"{tag}: detection classes differ")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    check(err <= 1e-4, f"{tag}: decoded values differ by {err}")


def yolo_chain_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro import workloads
    from repro.core import bnn_model

    tag = "yolov2_tiny@416 vpu_chain"
    imgs = frames(FRAME_SIZES)
    groups, buckets = (4, 1, 3), (1, 4)
    runs = {}
    for mode in ("vpu_chain", "xla"):
        wl = workloads.get("yolov2_tiny_voc", matmul_mode=mode, seed=SEED)
        server, reqs = serve(wl, imgs, buckets, groups, f"yolo {mode}")
        check_served(server, reqs, mode, f"yolo {mode}")
        runs[mode] = (wl, reqs)
    wl, reqs = runs["vpu_chain"]
    ref_wl, ref_reqs = runs["xla"]
    for r, ref in zip(reqs, ref_reqs):
        compare_decoded(r.result, ref.result, "detect", f"{tag} req {r.id}")
    survivors = [int(np.sum(np.asarray(r.result)[..., 4] > 0)) for r in reqs]
    print(f"[{tag}] surviving boxes per frame: {survivors}")
    x = np.stack([np.asarray(wl.preprocess_hook(im)) for im in imgs[:4]])
    for b in buckets:
        # the executables the server ran (tuned chain tiles included)
        exe = wl.engine.engine.compile(b, donate_input=True, mode="vpu_chain")
        ref_exe = ref_wl.engine.engine.compile(b, donate_input=True,
                                               mode="xla")
        check_executor(exe, wl.engine._plan_shape(b), "vpu_chain",
                       f"{tag} bucket {b}")
        words, err = compare_bucket(exe, ref_exe, x[:b], f"{tag} bucket {b}")
        print(f"[{tag} bucket {b}] packed tail bit-exact with xla "
              f"({words} words); logits max |diff| vs xla {err:.2e}")
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref_wl.engine.raw(jnp.asarray(x[:1])))
        oracle = np.asarray(bnn_model.float_forward(
            ref_wl.params, ref_wl.spec, jnp.asarray(x[:1])))
    err = float(np.max(np.abs(logits - oracle)))
    check(err <= 1e-3, f"{tag}: xla logits vs float_forward differ by {err}")
    print(f"[{tag}] {len(reqs)} frames served; xla logits vs "
          f"float_forward max |diff| {err:.2e}")


def alexnet_phase() -> None:
    from repro import workloads

    imgs = frames([(256, 256), (227, 227), (300, 400), (480, 360)],
                  seed=SEED + 1)
    runs = {}
    for mode in ("xla",) + PER_NODE_MODES:
        tag = f"alexnet@227 {mode}"
        wl = workloads.get("alexnet_imagenet", matmul_mode=mode, seed=SEED)
        server, reqs = serve(wl, imgs, (4,), (4,), tag)
        check_served(server, reqs, mode, tag)
        # the executable the server ran
        exe = wl.engine.engine.compile(4, donate_input=True, mode=mode)
        if mode != "xla":
            check_executor(exe, wl.engine._plan_shape(4), mode, tag)
        runs[mode] = (wl, exe, reqs)
    ref_wl, ref_exe, ref_reqs = runs["xla"]
    x = np.stack([np.asarray(ref_wl.preprocess_hook(im)) for im in imgs])
    for mode in PER_NODE_MODES:
        tag = f"alexnet@227 {mode}"
        _, exe, reqs = runs[mode]
        words, err = compare_bucket(exe, ref_exe, x, tag)
        for r, ref in zip(reqs, ref_reqs):
            compare_decoded(r.result, ref.result, "classify",
                            f"{tag} req {r.id}")
        print(f"[{tag}] packed tail bit-exact ({words} words); "
              f"logits max |diff| vs xla {err:.2e}")


def replica_phase(devices) -> None:
    """Four one-chip replica lanes vs one lane, bit-exact, each lane's
    output on its own device."""
    from repro import workloads
    from repro.distributed.replicas import ReplicaGroup
    from repro.serving import InferenceServer

    tag = "replicas x4 yolov2_tiny@416 vpu_chain"
    wl = workloads.get("yolov2_tiny_voc", matmul_mode="vpu_chain", seed=SEED)
    engine = wl.engine.engine
    kw = dict(max_batch=1, max_wait_s=0.0, buckets=(1,),
              preprocess=wl.preprocess_hook)
    group = ReplicaGroup(engine, devices[:4], **kw)
    for name, per in group.compile_buckets().items():
        for b, s in per.items():
            print(f"[{tag}] {name} compile bucket {b}: {s:.1f} s")
    imgs = frames(FRAME_SIZES)
    reqs = []
    for im in imgs:                  # one at a time: every lane, bucket 1
        reqs.append(group.submit(im))
        group.drain()
    lanes = {}
    for rep in group.replicas.values():
        check_served(rep.server, [], "vpu_chain", f"{tag} {rep.name}")
        lanes[rep.name] = sum(1 for rec in rep.server.flight.dump()
                              if rec.get("outcome") == "served")
        exe = rep.server._executable(1)
        out = exe(np.zeros(engine._plan_shape(1), np.uint8))
        got = set(out.devices())
        check(got == {rep.devices[0]},
              f"{tag}: {rep.name} output on {got}, not {rep.devices[0]}")
    check(sum(1 for n in lanes.values() if n) == 4,
          f"{tag}: lanes served {lanes}")
    one = InferenceServer(engine, **kw)
    ref = []
    for im in imgs:
        ref.append(one.submit(im))
        one.drain()
    for r, want in zip(reqs, ref):
        check(r.outcome == "served", f"{tag}: request {r.outcome}")
        check(np.array_equal(np.asarray(r.result), np.asarray(want.result)),
              f"{tag}: request {r.id} differs from the one-lane result")
    print(f"[{tag}] {len(reqs)} frames over lanes {lanes}: bit-exact "
          f"with one lane; each lane's output on its own device")


def shard_phase(devices) -> None:
    """``launch.serve --shard``: the data-parallel bucket over 4 devices
    against the unsharded bucket."""
    import dataclasses

    from repro import workloads
    from repro.launch.serve import shard_mesh
    from repro.serving import InferenceServer

    tag = "--shard x4 yolov2_tiny@416 vpu_chain"
    wl = workloads.get("yolov2_tiny_voc", matmul_mode="vpu_chain", seed=SEED)
    imgs = frames(FRAME_SIZES)
    results = {}
    for name, mesh in (("sharded", shard_mesh()), ("unsharded", None)):
        engine = dataclasses.replace(wl.engine.engine)
        server = InferenceServer(engine, max_batch=4, max_wait_s=0.0,
                                 buckets=(4,), mesh=mesh,
                                 preprocess=wl.preprocess_hook)
        for b, s in server.compile_buckets().items():
            print(f"[{tag}] {name} compile bucket {b}: {s:.1f} s")
        reqs = [server.submit(im) for im in imgs]
        server.drain()
        check_served(server, reqs, "vpu_chain", f"{tag} {name}")
        results[name] = np.stack([np.asarray(r.result) for r in reqs])
        if mesh is not None:
            check(server.data_parallel == 4,
                  f"{tag}: data_parallel {server.data_parallel}")
            out = server._executable(4)(server._place(
                np.zeros(engine._plan_shape(4), np.uint8)))
            check(set(out.devices()) == set(devices[:4]),
                  f"{tag}: sharded output on {out.devices()}")
    err = float(np.max(np.abs(results["sharded"] - results["unsharded"])))
    check(err <= 1e-4, f"{tag}: sharded vs unsharded differ by {err}")
    print(f"[{tag}] {len(imgs)} frames: sharded == unsharded "
          f"(max |diff| {err:.2e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    from repro.cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    try:
        if args.chips == 4:
            check(len(devices) >= 4,
                  f"--chips 4 needs 4 devices, found {len(devices)}")
            replica_phase(devices)
            shard_phase(devices)
        else:
            yolo_chain_phase()
            alexnet_phase()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
