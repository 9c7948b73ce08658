"""Sustained-load endurance harness (DESIGN.md §11.4).

Where the other serving benchmark measures *how fast*, this one measures
*whether it keeps working*: an open-loop request stream (arrivals on a
fixed schedule, independent of server progress — the arrival process a
real front end sees) is driven through the hardened
:class:`~repro.serving.server.InferenceServer` for long enough that
slow leaks and drift show up, under two scenarios:

* ``steady``    — no faults.  Asserts the boring invariants that make
  sustained serving possible: every request terminally resolves,
  ``engine.trace_count`` stays **flat** after warmup (the zero-retrace
  serving contract), RSS growth after warmup stays under a budget (no
  per-request leak), and the latency SLO attainment is reported.
* ``fault_storm`` — a seeded :class:`~repro.serving.faults.FaultPlan`
  injects transient device faults, a compile failure, preprocess
  errors and latency spikes while the same open-loop stream runs.
  Asserts availability (served / (served + errors)) stays above a
  floor, that demotions are visible in the flight records, and — after
  uninstalling the plan — that a sample of served results is
  **bit-exact** vs the engine's ``cross_check`` oracle: retries and
  backend demotions may change *when* a request is served, never *what*
  it returns.
* ``kill_recover`` — the crash-safety story (DESIGN.md §14.3): a child
  process boots from an AOT artifact, journals a request stream
  through a :class:`~repro.serving.recovery.RequestJournal`, serves
  part of it, then SIGKILLs itself mid-stream.  A second fresh process
  boots from the same artifact + journal, replays every
  journaled-but-unresolved request, and the row reports the recovered
  fraction, the recovery wall time, and that the restarted process
  served with **zero retraces** (artifact boot) — the kill-9 proof the
  journal exists for.

Writes ``BENCH_endurance.json`` (provenance-stamped like every BENCH
artifact).  ``--smoke`` is the CI-sized run; the full run rides
``python -m benchmarks.run``.  ``--phase export|kill|recover --dir D`` are the
subprocess phases of ``kill_recover`` (driven by the parent run, not by
hand).

    PYTHONPATH=src python -m benchmarks.endurance_bench [--smoke]
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from benchmarks.common import emit, is_skipped, skipped, write_bench


def rss_bytes() -> int | None:
    """Resident set size via /proc (None off Linux)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError):
        return None


def _make_server(watchdog_s: float | None = 10.0, artifact: str | None = None,
                 journal=None):
    from repro.core import bnn_model
    from repro.serving import InferenceServer, PhoneBitEngine, RetryPolicy

    spec = [bnn_model.BConv(3, 32, kernel=3, stride=1, pad=1, first=True),
            bnn_model.Pool(2, 2),
            bnn_model.BConv(32, 32, kernel=3, stride=1, pad=1),
            bnn_model.Pool(2, 2),
            bnn_model.FloatDense(4 * 4 * 32, 10)]
    params = bnn_model.init_params(jax.random.key(0), spec)
    # Serve one rung above the ladder floor so the storm's demotion
    # path (xla_pm1 → xla) is actually reachable — and bit-exact.
    engine = PhoneBitEngine.from_trained(params, spec, (16, 16),
                                         matmul_mode="xla_pm1")
    server = InferenceServer(
        engine, max_batch=4, max_wait_s=0.0, buckets=(1, 2, 4),
        retry=RetryPolicy(max_attempts=3, backoff_base_s=0.002,
                          backoff_cap_s=0.05),
        max_queue=512, watchdog_s=watchdog_s,
        artifact=artifact, journal=journal)
    return engine, server


def _open_loop(server, payloads: list[np.ndarray], rate_hz: float,
               deadline_s: float | None = None) -> list:
    """Drive an open-loop arrival process: request *i* is submitted at
    ``t0 + i/rate`` regardless of server progress (serving ticks fill
    the gaps), then the queue is drained.  Returns the requests."""
    reqs = []
    t0 = time.monotonic()
    for i, p in enumerate(payloads):
        due = t0 + i / rate_hz
        while time.monotonic() < due:
            server.step()
        reqs.append(server.submit(p, deadline_s=deadline_s))
    server.drain()
    return reqs


def _outcome_counts(reqs: list) -> dict:
    from repro.serving import OUTCOMES

    counts = {o: 0 for o in OUTCOMES}
    for r in reqs:
        counts[r.outcome] += 1
    return counts


def _check_bitexact(engine, server, served: list, sample: int = 8) -> dict:
    """Replay a sample of served requests through the ``cross_check``
    oracle (graph path asserted bit-exact vs the legacy flat walk) and
    compare bit-for-bit: resilience must never corrupt results.

    Two things legitimately vary with *when* a request was served, both
    last-ulp float-epilogue effects that never touch the packed binary
    layers: a demoted request ran a different ladder rung (pm1-family vs
    xor-family dense layers associate differently), and the bucket size
    its batch padded to changes XLA's reduction codegen for the float
    dense layer.  So each sample must be bit-identical to the reference
    of one (mode the server actually served under) × (compiled bucket)
    replay — and the configured mode additionally goes through the full
    ``cross_check`` oracle (graph vs legacy walk) on every sample."""
    modes = {engine.matmul_mode}
    if server.health is not None:
        modes.add(server.health.mode)
        for d in server.health.demotions:
            modes.update((d["from_mode"], d["to_mode"]))
    idx = np.linspace(0, len(served) - 1,
                      min(sample, len(served))).astype(int)
    checked = mismatches = 0
    for i in sorted(set(idx.tolist())):
        r = served[i]
        x1 = np.asarray(r.payload)
        engine.cross_check(x1[None])        # oracle: graph == legacy
        got = np.asarray(r.result)
        ok = False
        for m in sorted(modes):
            for b in server.scheduler.buckets:
                xb = np.zeros((b, *x1.shape), x1.dtype)
                xb[0] = x1
                want = np.asarray(engine.compile(b, mode=m)(xb))[0]
                if np.array_equal(got, want):
                    ok = True
                    break
            if ok:
                break
        checked += 1
        mismatches += not ok
    return {"checked": checked, "mismatches": int(mismatches),
            "modes": sorted(modes), "ok": mismatches == 0}


def _run_scenario(name: str, *, requests: int, rate_hz: float,
                  warmup: int, slo_ms: float, rss_budget_mb: float,
                  plan=None) -> dict:
    """One endurance scenario; never lets a serving failure escape —
    any exception that does is the exact bug this harness exists to
    catch, so it is counted, not masked."""
    from repro.obs import metrics as _obs_metrics
    from repro.serving import faults

    engine, server = _make_server()
    rng = np.random.default_rng(42)
    mk = lambda n: [rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
                    for _ in range(n)]

    server.compile_buckets()
    unhandled = 0
    with _obs_metrics.use_registry() as reg:
        # Warmup outside the measurement window: first-touch allocations
        # (numpy pools, jit dispatch caches) are not leaks.
        try:
            _open_loop(server, mk(warmup), rate_hz)
        except Exception:               # noqa: BLE001 — the bug we hunt
            unhandled += 1
        rss0, trace0 = rss_bytes(), engine.trace_count

        if plan is not None:
            faults.install(plan)
        t_start = time.monotonic()
        try:
            reqs = _open_loop(server, mk(requests), rate_hz)
        except Exception:               # noqa: BLE001
            unhandled += 1
            reqs = []
        finally:
            wall_s = time.monotonic() - t_start
            if plan is not None:
                faults.uninstall()

        rss1, trace1 = rss_bytes(), engine.trace_count
        injected = reg.snapshot().get("faults.injected", 0)

    counts = _outcome_counts(reqs) if reqs else {}
    terminal = all(r.done and r.outcome is not None for r in reqs)
    served = [r for r in reqs if r.outcome == "served"]
    n_err = counts.get("error", 0)
    availability = (len(served) / (len(served) + n_err)
                    if served or n_err else skipped("no samples"))
    m = server.metrics()
    slo_attained = (sum(1 for v in server._metrics.latencies
                        if v * 1e3 <= slo_ms)
                    / len(server._metrics.latencies)
                    if server._metrics.latencies
                    else skipped("no latency samples"))
    rss_growth = (rss1 - rss0) if rss0 is not None and rss1 is not None \
        else skipped("no /proc rss")
    # BackendHealth's own log is authoritative — the flight ring evicts
    # demotion rows once enough request rows follow them.
    demotion_rows = (list(server.health.demotions)
                     if server.health is not None else [])
    row = {
        "scenario": name,
        "requests": requests,
        "rate_hz": rate_hz,
        "wall_s": wall_s,
        "unhandled_exceptions": unhandled,
        "all_terminal": terminal,
        "outcomes": counts,
        "availability": availability,
        "p50_ms": m["p50_ms"], "p95_ms": m["p95_ms"],
        "slo_ms": slo_ms, "slo_attainment": slo_attained,
        "throughput": m["throughput"],
        "retries": m["retries"], "errors": m["errors"],
        "rejected": m["rejected"], "degraded": m["degraded"],
        "mode_final": m["mode"],
        "faults_injected": int(injected or 0),
        "trace_count": {"start": trace0, "end": trace1,
                        "flat": trace1 == trace0},
        "rss": {"start_bytes": rss0, "end_bytes": rss1,
                "growth_bytes": rss_growth,
                "budget_mb": rss_budget_mb,
                "flat": (is_skipped(rss_growth)
                         or rss_growth <= rss_budget_mb * 2**20)},
        "demotions": demotion_rows,
        "bitexact": (_check_bitexact(engine, server, served) if served
                     else {"checked": 0, "mismatches": 0, "ok": False}),
    }
    return row


def _storm_plan():
    """The seeded fault storm: two guaranteed early device faults (a
    deterministic demotion), then rate-based transient noise, one
    compile failure, sparse preprocess errors and latency spikes."""
    from repro.serving.faults import LATENCY_SPIKE, FaultPlan, FaultSpec

    return FaultPlan([
        # Pinned to one bucket: health ladders are per-bucket now
        # (DESIGN.md §14.3), so the guaranteed demotion needs both
        # guaranteed faults to land on the SAME ladder.
        FaultSpec("server.device", "device_fault", times=2,
                  match={"bucket": 4}),
        FaultSpec("server.device", "device_fault", rate=0.05, after=2),
        FaultSpec("executor.call", "device_oom", rate=0.03),
        FaultSpec("engine.compile", "compile_error", times=1, after=1),
        FaultSpec("server.preprocess", "preprocess_error", rate=0.02),
        FaultSpec("server.device", LATENCY_SPIKE, rate=0.05,
                  duration_s=0.002),
    ], seed=7)


def _phase_export(d: str) -> None:
    """First child of ``kill_recover``: export the AOT artifact both later
    phases boot from.  A child, not the parent, so that on a one-chip
    host every process that touches the device runs alone."""
    engine, _server = _make_server()
    engine.export_artifact(os.path.join(d, "artifact"), buckets=(1, 2, 4))


def _phase_kill(d: str) -> None:
    """Child half of ``kill_recover``: boot from the artifact, journal
    a request stream, serve a prefix of it, then SIGKILL ourselves with
    requests still unresolved — no atexit, no flush, no goodbye."""
    import signal

    from repro.serving.recovery import RequestJournal

    _engine, server = _make_server(
        artifact=os.path.join(d, "artifact"),
        journal=RequestJournal(os.path.join(d, "journal.jsonl")))
    rng = np.random.default_rng(7)
    for _ in range(24):
        server.submit(rng.integers(0, 256, (16, 16, 3), dtype=np.uint8))
    for _ in range(6):          # resolve a prefix of the stream
        server.step(force=True)
    os.kill(os.getpid(), signal.SIGKILL)


def _phase_recover(d: str) -> None:
    """Restart half of ``kill_recover``: a fresh process boots from the
    same artifact + journal, replays every journaled-but-unresolved
    request, and reports what it recovered (JSON on stdout)."""
    import json

    from repro.serving.recovery import RequestJournal, replay_journal

    t0 = time.monotonic()
    jpath = os.path.join(d, "journal.jsonl")
    pre = RequestJournal.scan(jpath)
    engine, server = _make_server(
        artifact=os.path.join(d, "artifact"),
        journal=RequestJournal(jpath))
    reqs = replay_journal(server, jpath)
    server.drain()
    recovery_s = time.monotonic() - t0
    post = RequestJournal.scan(jpath)
    print(json.dumps({
        "journaled_unresolved": len(pre.unresolved),
        "torn_tail": pre.torn_tail,
        "replayed": len(reqs),
        "recovered": sum(1 for r in reqs if r.outcome == "served"),
        "outcomes": _outcome_counts(reqs),
        "unresolved_after": len(post.unresolved),
        "trace_count": engine.trace_count,
        "recovery_s": recovery_s,
    }))


def _kill_recover_scenario(smoke: bool) -> dict:
    """Drive the two subprocess phases and assemble the row.  A spawn
    environment that cannot run subprocesses yields a skipped row, not
    a crash (the CI job asserts the row is NOT skipped)."""
    import json
    import subprocess
    import sys
    import tempfile

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d = tempfile.mkdtemp(prefix="endurance_killrec_")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         *filter(None, [env.get("PYTHONPATH")])])
    env["REPRO_AUTOTUNE_CACHE"] = "0"

    def phase(name: str, timeout: float):
        return subprocess.run(
            [sys.executable, "-m", "benchmarks.endurance_bench",
             "--phase", name, "--dir", d],
            capture_output=True, text=True, timeout=timeout, env=env,
            cwd=root)

    row: dict = {"scenario": "kill_recover", "requests": 24}
    try:
        p_exp = phase("export", 420)
    except Exception as e:              # noqa: BLE001 — report, don't crash
        row["ok"] = skipped(f"export phase spawn failed: {e}")
        return row
    if p_exp.returncode != 0:
        row["ok"] = False
        row["error"] = (f"export phase exited {p_exp.returncode}: "
                        f"{p_exp.stderr[-500:]}")
        return row
    try:
        p_kill = phase("kill", 420)
    except Exception as e:              # noqa: BLE001 — report, don't crash
        row["ok"] = skipped(f"kill phase spawn failed: {e}")
        return row
    killed = p_kill.returncode == -9
    row["killed"] = killed
    if not killed:
        row["ok"] = False
        row["error"] = (f"kill phase exited {p_kill.returncode}: "
                        f"{p_kill.stderr[-500:]}")
        return row
    try:
        p_rec = phase("recover", 420)
        rec = json.loads(p_rec.stdout.strip().splitlines()[-1])
    except Exception as e:              # noqa: BLE001
        row["ok"] = False
        row["error"] = f"recover phase failed: {e}"
        return row
    row.update(rec)
    # The §14.3 contract: every journaled-unresolved request is
    # replayed and terminally resolved, the restarted process serves
    # with zero retraces (artifact boot), and nothing stays open.
    row["recovered_fraction"] = (
        rec["recovered"] / rec["journaled_unresolved"]
        if rec["journaled_unresolved"] else 1.0)
    row["ok"] = (rec["replayed"] == rec["journaled_unresolved"]
                 and rec["recovered"] == rec["journaled_unresolved"]
                 and rec["unresolved_after"] == 0
                 and rec["trace_count"] == 0)
    return row


def run(smoke: bool = False, out: str = "BENCH_endurance.json") -> dict:
    n = 64 if smoke else 500
    rate = 400.0 if smoke else 250.0
    # The subprocess scenario runs first, while this process has not yet
    # touched a device: its children must be able to take the chip.
    killrec = _kill_recover_scenario(smoke)
    scenarios = [
        _run_scenario("steady", requests=n, rate_hz=rate,
                      warmup=16, slo_ms=250.0, rss_budget_mb=64.0),
        _run_scenario("fault_storm", requests=n, rate_hz=rate,
                      warmup=16, slo_ms=500.0, rss_budget_mb=64.0,
                      plan=_storm_plan()),
        killrec,
    ]
    steady = scenarios[0]
    storm = scenarios[1]
    killrec = scenarios[2]
    loop = scenarios[:2]                # the open-loop rows
    summary = {
        "unhandled_exceptions": sum(s["unhandled_exceptions"]
                                    for s in loop),
        "all_terminal": all(s["all_terminal"] for s in loop),
        "steady_flat_trace": steady["trace_count"]["flat"],
        "steady_flat_rss": steady["rss"]["flat"],
        "storm_availability": storm["availability"],
        "storm_availability_floor": 0.95,
        "storm_demotions": len(storm["demotions"]),
        "bitexact_ok": all(s["bitexact"]["ok"] for s in loop),
        "kill_recover_ok": killrec["ok"],
        "kill_recovered_fraction": killrec.get("recovered_fraction"),
        "kill_recovery_s": killrec.get("recovery_s"),
        "ok": (
            sum(s["unhandled_exceptions"] for s in loop) == 0
            and all(s["all_terminal"] for s in loop)
            and steady["trace_count"]["flat"]
            and steady["rss"]["flat"]
            and (storm["availability"]
                 if isinstance(storm["availability"], float) else 0) >= 0.95
            and all(s["bitexact"]["ok"] for s in loop)
            and killrec["ok"] is True
        ),
    }
    report = {
        "device": f"{jax.default_backend()}:"
                  f"{jax.devices()[0].device_kind}",
        "smoke": smoke,
        "scenarios": scenarios,
        "summary": summary,
    }
    report = write_bench(out, report)

    emit([{
        "scenario": s["scenario"], "req": s["requests"],
        "served": s["outcomes"].get("served", ""),
        "errors": s["errors"], "retries": s["retries"],
        "avail": (f"{s['availability']:.3f}"
                  if isinstance(s["availability"], float) else ""),
        "p95_ms": (f"{s['p95_ms']:.1f}"
                   if s["p95_ms"] is not None else ""),
        "flat_trace": s["trace_count"]["flat"],
        "rss_mb": (f"{s['rss']['growth_bytes'] / 2**20:.1f}"
                   if isinstance(s["rss"]["growth_bytes"], int) else ""),
        "demotions": len(s["demotions"]),
        "bitexact": s["bitexact"]["ok"],
    } for s in loop], "§Endurance: sustained load + fault storm")
    emit([{
        "scenario": killrec["scenario"], "req": killrec.get("requests"),
        "killed": killrec.get("killed", ""),
        "journaled": killrec.get("journaled_unresolved", ""),
        "recovered": killrec.get("recovered", ""),
        "fraction": (f"{killrec['recovered_fraction']:.2f}"
                     if isinstance(killrec.get("recovered_fraction"),
                                   float) else ""),
        "traces": killrec.get("trace_count", ""),
        "recovery_s": (f"{killrec['recovery_s']:.1f}"
                       if isinstance(killrec.get("recovery_s"), float)
                       else ""),
        "ok": killrec["ok"],
    }], "§Endurance: kill -9 → artifact + journal restart")
    print(f"wrote {out} (ok={summary['ok']}, storm availability="
          f"{summary['storm_availability']}, kill_recover="
          f"{summary['kill_recover_ok']})")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmarks.endurance_bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run; still writes BENCH_endurance.json")
    ap.add_argument("--phase", choices=("export", "kill", "recover"),
                    help="subprocess halves of kill_recover (internal)")
    ap.add_argument("--dir", dest="dir_",
                    help="shared artifact+journal dir for --phase")
    args = ap.parse_args(argv)
    if args.phase:
        if not args.dir_:
            ap.error("--phase requires --dir")
        {"export": _phase_export, "kill": _phase_kill,
         "recover": _phase_recover}[args.phase](args.dir_)
        return
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
