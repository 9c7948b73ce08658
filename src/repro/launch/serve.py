"""Serving drivers on the production serving subsystem (DESIGN.md §7).

Both modes front their engine with the shared server protocol
(submit/poll/drain + metrics):

* ``--mode bnn``  — PhoneBit engine (Fig 2/3) behind an
  :class:`~repro.serving.server.InferenceServer`: per-bucket precompiled
  executables (no manual warm-up), async double-buffered dispatch
  (``--sync`` for the blocking baseline), optional data-parallel batch
  sharding over the host devices (``--shard``).
* ``--workload``  — serve a registered end-to-end workload
  (``repro.workloads``): arbitrary-size images go through the workload's
  preprocess hook, and the server scatters *decoded* predictions (top-k
  labels / NMS'd boxes) instead of raw logits.
* ``--mode lm``   — continuous-batching decode through the LMServer's
  identical submit/drain surface.
* ``--export-artifact PATH`` / ``--artifact PATH`` — the zero-warmup
  pair (DESIGN.md §12): export AOT bucket executables offline, then
  boot the server from them with zero serve-time traces.
* ``--workloads a,b,c`` — multi-tenant serving: each entry
  (``name[:weight]``) becomes a weighted-fair lane behind one
  :class:`~repro.serving.multiplex.MultiTenantServer`.
* ``--journal PATH`` — durable request journal (DESIGN.md §14.3):
  accepted submits are WAL-journaled before they enqueue, and a boot
  over an existing journal replays whatever a crashed predecessor left
  unresolved.

    PYTHONPATH=src python -m repro.launch.serve --mode bnn \
        --network yolov2-tiny --requests 32
    PYTHONPATH=src python -m repro.launch.serve --mode bnn \
        --workload yolov2_tiny_voc --input-hw 64 --requests 8
    PYTHONPATH=src python -m repro.launch.serve \
        --workload alexnet_imagenet --export-artifact /tmp/alex.art
    PYTHONPATH=src python -m repro.launch.serve \
        --workload alexnet_imagenet --artifact /tmp/alex.art
    PYTHONPATH=src python -m repro.launch.serve \
        --workloads alexnet_imagenet:3,vgg16_imagenet --requests 8
    PYTHONPATH=src python -m repro.launch.serve --mode lm --requests 4
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.cache import configure_compile_cache
from repro.distributed.sharding import rules_for_mesh
from repro.launch.mesh import make_host_mesh
from repro.models import paper_nets, transformer
from repro.obs import trace as obs_trace
from repro.serving import InferenceServer, PhoneBitEngine, buckets_for
from repro.serving.lm_server import LMServer


def _print_metrics(tag: str, m: dict) -> None:
    lat = (f"p50 {m['p50_ms']:.1f} ms, p95 {m['p95_ms']:.1f} ms"
           if m.get("p50_ms") is not None else "no latency samples")
    thr = (f"{m['throughput']:.1f}/s" if m.get("throughput") else "n/a")
    print(f"[{tag}] served {m['served']} (dropped {m['dropped']}), "
          f"{lat}, throughput {thr}")
    # Resilience counters (DESIGN.md §11) — only noisy when nonzero.
    res = {k: m[k] for k in ("retries", "errors", "rejected", "degraded")
           if m.get(k)}
    if res:
        mode = f", mode {m['mode']}" if m.get("mode") else ""
        print(f"[{tag}] resilience: "
              + ", ".join(f"{k} {v}" for k, v in res.items()) + mode)


def shard_mesh():
    """The ``--shard`` data-parallel mesh over every local device; one
    device has nothing to shard over, so that is an error, not a no-op."""
    n = len(jax.devices())
    if n < 2:
        raise SystemExit(f"--shard needs at least 2 devices, found {n}")
    return make_host_mesh(data=n, model=1)


def serve_bnn(args) -> dict:
    workload = None
    if args.workload:
        from repro import workloads

        workload = workloads.get(args.workload,
                                 variant=args.variant,
                                 matmul_mode=args.matmul_mode,
                                 input_hw=args.input_hw or None)
        engine, (h, w) = workload.engine, workload.input_hw
        print(f"{workload.name}: packed model "
              f"{workload.model_bytes / 2**20:.1f} MiB, input {h}x{w}, "
              f"task {workload.task}")
    else:
        spec, (h, w, c), params = paper_nets.init(args.network)
        if args.input_hw:      # fully-conv nets serve any resolution
            h = w = args.input_hw
        engine = PhoneBitEngine.from_trained(params, spec, (h, w),
                                             matmul_mode=args.matmul_mode)
        print(f"{args.network}: packed model "
              f"{engine.model_bytes / 2**20:.1f} MiB, input {h}x{w}")
    if args.export_artifact:
        # Offline half of zero-warmup serving: write the AOT bucket
        # executables + autotune table and exit.
        meta = engine.export_artifact(
            args.export_artifact, buckets_for(args.batch),
            **({"workload": workload.name} if workload else {}))
        print(f"[bnn] exported artifact {args.export_artifact} "
              f"(buckets {sorted(int(b) for b in meta['buckets'])}, "
              f"mode {meta['mode']})")
        return meta

    mesh = shard_mesh() if args.shard else None
    journal = None
    if args.journal:
        from repro.serving.recovery import RequestJournal, replay_journal

        journal = RequestJournal(args.journal)
    server = InferenceServer(
        engine, max_batch=args.batch, max_wait_s=0.0,
        buckets=buckets_for(args.batch),
        async_dispatch=not args.sync, mesh=mesh,
        preprocess=workload.preprocess_hook if workload else None,
        max_queue=args.max_queue or None,
        watchdog_s=args.watchdog_s,
        artifact=args.artifact,
        journal=journal)
    if journal is not None:
        # Crash recovery (DESIGN.md §14.3): requests journaled by a
        # previous process but never resolved are resubmitted first.
        replayed = replay_journal(server, args.journal)
        if replayed:
            print(f"[bnn] journal {args.journal}: replaying "
                  f"{len(replayed)} unresolved request(s)")
    if args.artifact:
        rep = server.artifact_report
        print(f"[bnn] artifact {args.artifact}: loaded buckets "
              f"{rep['loaded']}, missed {dict(rep['missed'])}")
    else:
        compile_s = server.compile_buckets()
        print(f"compiled buckets {list(compile_s)} in "
              f"{sum(compile_s.values()):.2f}s")

    plan = None
    if args.fault_storm:
        # Demo the resilience layer end to end: seeded transient device
        # faults + latency spikes while the request stream flows.
        from repro.serving.faults import FaultPlan, FaultSpec, install

        plan = install(FaultPlan([
            FaultSpec("server.device", "device_fault", times=2),
            FaultSpec("server.device", "device_fault", rate=0.1, after=2),
            FaultSpec("server.device", "latency_spike", rate=0.1,
                      duration_s=0.002),
        ], seed=7))
        print("[bnn] fault storm installed (seed 7)")

    rng = np.random.default_rng(0)
    # Workload requests arrive at an off-network size to exercise the
    # preprocess hook; raw-engine requests arrive network-sized.
    req_hw = (h + h // 2, w * 2) if workload else (h, w)
    reqs = []
    for _ in range(args.requests):
        reqs.append(server.submit(
            rng.integers(0, 256, (*req_hw, 3), dtype=np.uint8),
            deadline_s=args.deadline_s))
    server.drain()
    if plan is not None:
        from repro.serving import faults

        faults.uninstall()
        print(f"[bnn] storm: {len(plan.log)} faults injected, "
              f"{len(server.health.demotions)} demotions")
    m = server.metrics()
    _print_metrics("bnn", m)
    if args.artifact:
        print(f"[bnn] serve-time traces: {engine.trace_count}")
    if workload is not None:
        first = next((r for r in reqs if r.result is not None), None)
        if first is not None:
            preds = workload.format(first.result)
            print(f"[bnn] request 0 -> {len(preds)} predictions; "
                  f"top: {preds[:3]}")
    assert sum(r.done for r in reqs) >= args.requests
    return m


def serve_multi(args) -> dict:
    """Multi-tenant serving: each ``--workloads`` entry (name[:weight])
    is a weighted-fair lane behind one MultiTenantServer."""
    from repro import workloads
    from repro.serving import MultiTenantServer

    mux = MultiTenantServer(max_batch=args.batch, max_wait_s=0.0,
                            buckets=buckets_for(args.batch),
                            max_queue=args.max_queue or None,
                            watchdog_s=args.watchdog_s)
    wls = {}
    for entry in args.workloads.split(","):
        name, _, w = entry.strip().partition(":")
        weight = float(w) if w else 1.0
        wl = workloads.get(name, variant=args.variant,
                           matmul_mode=args.matmul_mode,
                           input_hw=args.input_hw or None)
        wls[name] = wl
        mux.add_workload(name, wl, weight=weight)
        print(f"[mux] tenant {name}: weight {weight}, "
              f"input {wl.input_hw[0]}x{wl.input_hw[1]}, task {wl.task}")

    rng = np.random.default_rng(0)
    reqs = {name: [] for name in wls}
    for _ in range(args.requests):
        for name, wl in wls.items():
            h, w = wl.input_hw
            reqs[name].append(mux.submit(
                name,
                rng.integers(0, 256, (h + h // 2, w * 2, 3),
                             dtype=np.uint8),
                deadline_s=args.deadline_s))
    mux.drain()
    m = mux.metrics()
    for name in wls:
        _print_metrics(f"mux:{name}", m["tenants"][name])
    ledger = ", ".join(
        f"{name} {f['dispatched_rows']} rows (w={f['weight']})"
        for name, f in m["fairness"].items())
    print(f"[mux] fairness: {ledger}")
    assert all(r.done for rs in reqs.values() for r in rs)
    return m


def serve_lm(args) -> dict:
    cfg = transformer.LMConfig(
        name="lm-serve-demo", n_layers=4, d_model=256, n_heads=8,
        n_kv_heads=4, d_head=32, d_ff=512, vocab=1024,
        tie_embeddings=True)
    mesh = make_host_mesh(data=1, model=len(jax.devices()))
    rules = rules_for_mesh(mesh)
    with mesh:
        params = jax.jit(
            lambda k: transformer.init_params(k, cfg, ep=rules.tp,
                                              vocab_pad_to=rules.tp),
            out_shardings=rules.tree_shardings(
                transformer.param_specs(cfg, rules)))(jax.random.key(0))
        server = LMServer(cfg=cfg, rules=rules, params=params,
                          n_slots=args.batch, max_seq=args.max_seq)
        rng = np.random.default_rng(0)
        reqs = [server.submit(list(rng.integers(1, cfg.vocab, size=8)),
                              max_new=args.max_new)
                for _ in range(args.requests)]
        done = server.drain()
        assert all(r.done for r in reqs) and len(done) == len(reqs)
        m = server.metrics()
        toks = sum(len(r.result) for r in reqs if r.result)
        _print_metrics("lm", m)
        print(f"[lm] {toks} tokens, kv utilization "
              f"{m['kv_utilization']:.0%}")
        return m


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("bnn", "lm"), default="bnn")
    ap.add_argument("--network", default="yolov2-tiny")
    ap.add_argument("--workload", default=None,
                    help="serve a registered end-to-end workload "
                         "(repro.workloads: e.g. yolov2_tiny_voc) — "
                         "preprocess hook + decoded predictions")
    ap.add_argument("--workloads", default=None, metavar="A[:W],B[:W]",
                    help="multi-tenant serving: comma-separated "
                         "workload names, each optionally :weighted "
                         "(e.g. alexnet_imagenet:3,vgg16_imagenet) — "
                         "one weighted-fair lane per entry")
    ap.add_argument("--matmul-mode", default="xla")
    ap.add_argument("--variant", default="paper",
                    help="workload variant (paper | tiny)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--input-hw", type=int, default=0,
                    help="override input resolution (fully-conv nets; "
                         "0 = the paper's)")
    ap.add_argument("--sync", action="store_true",
                    help="synchronous dispatch (baseline; default is "
                         "async double-buffered)")
    ap.add_argument("--shard", action="store_true",
                    help="data-parallel batch sharding over host devices")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission: submits beyond this queue "
                         "depth resolve rejected (0 = unbounded)")
    ap.add_argument("--watchdog-s", type=float, default=None,
                    help="bound each device readback; a stalled "
                         "executable resolves error instead of hanging")
    ap.add_argument("--fault-storm", action="store_true",
                    help="install a seeded fault plan (transient device "
                         "faults + latency spikes) to demo retry/"
                         "degrade — bnn mode only")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--export-artifact", default=None, metavar="PATH",
                    help="export AOT bucket executables + autotune "
                         "table to this directory and exit (the "
                         "offline half of zero-warmup serving)")
    ap.add_argument("--artifact", default=None, metavar="PATH",
                    help="boot the server from an exported artifact: "
                         "executables deserialize instead of tracing "
                         "(zero serve-time compiles)")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="durable request journal (JSONL WAL, DESIGN.md "
                         "§14.3): accepted submits hit disk before they "
                         "enqueue; on boot, unresolved requests from a "
                         "crashed process are replayed — bnn mode only")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record serving-stage spans and write a "
                         "Chrome/Perfetto trace-event JSON here "
                         "(chrome://tracing / ui.perfetto.dev)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    tracer = obs_trace.install() if args.trace_out else None
    try:
        if args.workloads:
            return serve_multi(args)
        if args.mode == "bnn":
            return serve_bnn(args)
        return serve_lm(args)
    finally:
        if tracer is not None:
            obs_trace.uninstall()
            tracer.export(args.trace_out)
            print(f"wrote {len(tracer.events)} trace events to "
                  f"{args.trace_out}")


if __name__ == "__main__":
    main()
