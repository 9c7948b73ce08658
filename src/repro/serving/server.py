"""InferenceServer: the production serving subsystem (DESIGN.md §7, §11).

One object owns the whole serve path the paper's phone loop inlines:

* a :class:`~repro.serving.scheduler.BatchScheduler` assembling
  deadline-aware, bucket-padded batches;
* the engine's **per-bucket executable cache** —
  ``compile_buckets()`` precompiles (and, in ``auto`` mode, autotunes)
  one :class:`GraphExecutor` per bucket so serve time never retraces;
* **async double-buffered dispatch** — batch *k+1* is dispatched while
  batch *k*'s device work is still in flight; the host blocks only when
  scattering results (``np.asarray`` at the pop of the one-deep pipeline),
  and each batch's input buffer is donated to the device;
* optional **data-parallel batch sharding** — given a mesh, inputs are
  placed with ``jax.sharding.NamedSharding(mesh, P(data_axis))`` so XLA
  splits every bucket across the data axis; buckets are rounded up to
  shard evenly and autotuning runs at the per-device shard shape (reusing
  the single-device winners).

The server surface is the protocol both serving paths share (the LM
decode server implements the same one): ``submit`` / ``poll`` / ``step``
/ ``drain`` plus ``metrics()`` (p50/p95 latency, queue depth, throughput,
dropped count — definitions in DESIGN.md §7.4).

Resilience (DESIGN.md §11): every request **terminally resolves** —
``done=True`` with ``outcome`` ∈ {served, shed, error, rejected} — and
no failure escapes ``step()`` to kill the serve loop:

* ``submit`` validates payloads against the engine's input spec and
  applies bounded-queue admission control, returning a structured
  ``rejected`` request instead of raising or poisoning a batch;
* a failed batch (compile error, device fault, preprocess exception)
  retries per-request with capped exponential backoff + jitter
  (:class:`~repro.serving.faults.RetryPolicy`) on the server's
  injectable clock, resolving ``error`` when attempts are exhausted;
* repeated executable failures demote the serving mode down
  :data:`~repro.serving.faults.DEGRADE_LADDER`
  (:class:`~repro.serving.faults.BackendHealth`): the failing backend
  is quarantined and re-probed periodically, demotions are published
  via the ``serve.degraded`` counter and flight-recorder records;
* an optional dispatch watchdog (``watchdog_s``) bounds the device
  readback so a wedged executable surfaces as an error, and ``drain``
  is iteration-bounded so a wedged queue cannot hang it forever.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

# Canonical home of the latency math and serving metrics is the
# observability layer (DESIGN.md §10); re-exported here for the existing
# import surface.
from repro.obs import FlightRecorder
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _trace
from repro.obs.metrics import ServingMetrics, percentile  # noqa: F401
from repro.serving import faults as _faults
from repro.serving.faults import (BackendHealth, BucketHealth,  # noqa: F401
                                  RetryPolicy, WatchdogTimeout)
from repro.serving.scheduler import BatchScheduler, Request


@runtime_checkable
class Server(Protocol):
    """What a serving front end looks like, BNN or LM."""

    def submit(self, payload: Any, **kw) -> Request: ...

    def poll(self, request: Request) -> bool: ...

    def drain(self) -> list[Request]: ...

    def metrics(self) -> dict: ...


class _InFlight:
    """One dispatched batch: requests + the device array still computing.

    ``row_idx`` maps each request to its row of the device output (rows
    of requests whose preprocessing failed are zero-filled and skipped);
    ``mode`` is the backend the executable ran under (degradation);
    ``t_dispatch``/``stage_s`` feed the flight recorder at scatter."""

    __slots__ = ("batch", "row_idx", "out", "bucket", "t_dispatch",
                 "stage_s", "mode", "probing")

    def __init__(self, batch: list[Request], row_idx: list[int], out,
                 bucket: int, t_dispatch: float, stage_s: float,
                 mode: str | None, probing: bool = False):
        self.batch = batch
        self.row_idx = row_idx
        self.out = out
        self.bucket = bucket
        self.t_dispatch = t_dispatch
        self.stage_s = stage_s
        self.mode = mode
        self.probing = probing


class InferenceServer:
    """Batched image-inference front end over a PhoneBitEngine.

    Parameters
    ----------
    engine:          a :class:`~repro.serving.engine.PhoneBitEngine` (or
                     anything with ``compile(bs, donate_input=,
                     data_parallel=, mode=) -> callable`` and
                     ``_plan_shape``).
    buckets:         compiled batch sizes; mixed-size traffic is padded up
                     to the nearest one.
    async_dispatch:  double-buffer dispatch (the default); ``False`` gives
                     the synchronous drain loop (benchmark baseline).
    preprocess:      optional per-payload host transform (decode / crop /
                     normalize) applied at batch staging.  Under async
                     dispatch batch k+1's preprocessing runs while batch
                     k's device work is in flight — host preprocessing is
                     the classic serving cost double-buffering hides.
    mesh/data_axis:  optional device mesh for data-parallel sharding.
    placement:       optional placement object (DESIGN.md §13), the
                     generalized form of ``mesh=``: duck-typed on
                     ``.kind`` so this module never imports
                     ``repro.distributed``.  ``kind == "data"``
                     (:class:`~repro.distributed.sharding.DataParallel`)
                     supplies mesh + axis; ``kind == "pipeline"``
                     (:class:`~repro.distributed.pipeline.Pipelined`)
                     compiles every bucket as a
                     :class:`~repro.runtime.placement.StagedExecutor`
                     over its devices.
    flight_capacity: size of the flight-recorder ring (recent request
                     records for postmortems; ``server.flight.dump()``).
    clock:           injectable monotonic clock (tests use a fake).

    Resilience (DESIGN.md §11)
    --------------------------
    retry:           :class:`RetryPolicy` for failed batches (None = one
                     attempt, no retry).  Backoff is applied by stamping
                     ``Request.not_before`` on the server clock.
    max_queue:       bounded admission: submits beyond this queue depth
                     resolve ``rejected`` (None = unbounded).
    validate:        payload validation at ``submit`` (shape vs the
                     engine input spec when no preprocess hook rewrites
                     sizes, object-dtype and NaN/Inf checks).
    degrade:         demote the serving backend down ``DEGRADE_LADDER``
                     after ``demote_after`` consecutive executable
                     failures; quarantined modes re-probe after
                     ``probe_after_s`` (doubling per re-offense).
    watchdog_s:      bound the device readback; a stalled executable
                     raises :class:`WatchdogTimeout` into the normal
                     retry/error path (None = block forever, the
                     pre-resilience behavior and the zero-thread path).
    sleep:           how ``drain`` waits out retry backoff when every
                     queued request is ineligible (tests inject a fake
                     that advances their fake clock).
    tenant:          optional tenant name stamped onto flight-recorder
                     records, fault contexts and ``metrics()`` — how
                     :class:`~repro.serving.multiplex.MultiTenantServer`
                     labels each lane.
    artifact:        optional AOT artifact directory (DESIGN.md §12):
                     restore serialized bucket executables at
                     construction so serving starts with zero traces;
                     per-bucket meta mismatches fall back to live
                     compile with an ``artifact.miss`` event.

    Observability (DESIGN.md §10): when a tracer is installed
    (``repro.obs.trace.install()``) each serving stage emits a span —
    ``serve.submit`` (instant), ``serve.assemble``, ``serve.stage``,
    ``serve.dispatch``, ``serve.device``, ``serve.scatter`` — all
    host-side, so tracing never retraces the compiled executables.
    Disabled (the default), every site is one global read.
    """

    def __init__(self, engine, *, max_batch: int = 8,
                 max_wait_s: float = 0.0,
                 buckets: tuple[int, ...] = (1, 2, 4, 8),
                 async_dispatch: bool = True,
                 donate_input: bool = True,
                 preprocess: Callable[[np.ndarray], np.ndarray]
                 | None = None,
                 mesh=None, data_axis: str = "data",
                 placement=None,
                 flight_capacity: int = 256,
                 clock: Callable[[], float] = time.monotonic,
                 retry: RetryPolicy | None = RetryPolicy(),
                 max_queue: int | None = None,
                 validate: bool = True,
                 degrade: bool = True,
                 demote_after: int = 2,
                 probe_after_s: float = 30.0,
                 watchdog_s: float | None = None,
                 sleep: Callable[[float], None] | None = None,
                 tenant: str | None = None,
                 artifact: str | None = None,
                 journal=None):
        self.engine = engine
        self.tenant = tenant
        # Durable request journal (DESIGN.md §14.3): accepted submits are
        # WAL-journaled before they enter the scheduler; terminal
        # resolutions close them.  ``recovery.replay_journal`` resubmits
        # unresolved records after a crash.
        self.journal = journal
        self.preprocess = preprocess
        # Placement generalizes mesh=: duck-typed on .kind so the server
        # never imports repro.distributed (which imports this module).
        self.placement = placement
        self.pipeline_devices: tuple | None = None
        if placement is not None:
            kind = getattr(placement, "kind", None)
            if kind == "data":
                if mesh is not None:
                    raise ValueError("pass placement= or mesh=, not both")
                mesh, data_axis = placement.mesh, placement.axis
            elif kind == "pipeline":
                if mesh is not None:
                    raise ValueError("pipeline placement and mesh= are "
                                     "mutually exclusive on one server; "
                                     "compose replicas of pipelines via "
                                     "ReplicaGroup")
                self.pipeline_devices = tuple(placement.devices)
            else:
                raise ValueError(f"placement {placement!r} has no valid "
                                 f".kind ('data' | 'pipeline')")
        self.mesh, self.data_axis = mesh, data_axis
        self.data_parallel = int(mesh.shape[data_axis]) if mesh is not None \
            else 1
        if self.data_parallel > 1:
            dp = self.data_parallel
            buckets = tuple(sorted({-(-b // dp) * dp for b in buckets}))
            max_batch = max(max_batch, buckets[0])
        self.scheduler = BatchScheduler(
            max_batch=max_batch, max_wait_s=max_wait_s,
            buckets=tuple(buckets))
        self.async_dispatch = async_dispatch
        self.donate_input = donate_input
        self.clock = clock
        self.retry = retry
        self.max_queue = max_queue
        self.validate = validate
        self.watchdog_s = watchdog_s
        self._sleep = sleep if sleep is not None \
            else (lambda s: time.sleep(min(s, 0.05)))
        # Per-bucket degradation ladders (DESIGN.md §14.3): one
        # pathological bucket shape demotes only its own ladder;
        # ``health.mode`` is the worst bucket's rung (the PR 7 surface).
        self.health = BucketHealth(
            engine.matmul_mode, demote_after=demote_after,
            probe_after_s=probe_after_s) if degrade else None
        self._pending: _InFlight | None = None
        # Requests resolved ``error`` since the last step() returned —
        # terminal completions, so step/drain hand them back to callers
        # alongside the served ones.
        self._errored: list[Request] = []
        self._metrics = ServingMetrics(clock)
        # Postmortem ring of recent request records (DESIGN.md §10.3);
        # multi-tenant lanes stamp their tenant onto every record.
        self.flight = FlightRecorder(
            flight_capacity,
            tags={"tenant": tenant} if tenant is not None else None)
        # Rows dispatched to the device since construction (padded bucket
        # rows, i.e. what the accelerator actually paid for) — the cost
        # signal weighted-fair multiplexing charges each tenant's vtime.
        self.dispatched_rows = 0
        # AOT artifact restore (DESIGN.md §12): load serialized bucket
        # executables before the first request so serving starts with
        # zero traces; per-bucket misses fall back to live compile.
        self.artifact_report: dict | None = None
        if artifact is not None:
            self.artifact_report = engine.load_artifact(
                artifact, donate_input=donate_input,
                data_parallel=self.data_parallel,
                buckets=tuple(self.scheduler.buckets))

    # ---- executable cache -------------------------------------------------
    def _executable(self, bucket: int, mode: str | None = None):
        kw = {}
        if self.pipeline_devices is not None:
            kw["pipeline"] = self.pipeline_devices
        if self.data_parallel > 1:
            kw.update(mesh=self.mesh, data_axis=self.data_axis)
        return self.engine.compile(bucket, donate_input=self.donate_input,
                                   data_parallel=self.data_parallel,
                                   mode=mode, **kw)

    def compile_buckets(self) -> dict[int, float]:
        """Precompile (and autotune) every bucket; returns seconds spent
        per bucket.  After this, serving any mixed-size request stream
        triggers zero retraces (``engine.trace_count`` stays flat)."""
        timings: dict[int, float] = {}
        for b in self.scheduler.buckets:
            with _trace.span("compile.bucket", "compile", bucket=b,
                             data_parallel=self.data_parallel):
                t0 = time.perf_counter()
                exe = self._executable(b)
                x = self._place(np.zeros(self.engine._plan_shape(b),
                                         np.uint8))
                jax.block_until_ready(exe(x))
                timings[b] = time.perf_counter() - t0
        return timings

    # ---- placement --------------------------------------------------------
    def _place(self, x_np: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(x_np)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return jax.device_put(x_np, NamedSharding(self.mesh,
                                                  P(self.data_axis)))

    # ---- admission --------------------------------------------------------
    def _payload_error(self, payload: Any) -> str | None:
        """Why this payload cannot be served, or None when it can.

        Checked against the engine's input spec at the protocol edge so
        a malformed payload resolves alone instead of poisoning the
        whole assembled bucket batch it would have ridden in."""
        try:
            arr = np.asarray(payload)
        except Exception as e:          # noqa: BLE001 — any failure rejects
            return f"payload is not array-like: {e}"
        if not np.issubdtype(arr.dtype, np.number):
            # object arrays, strings, datetimes, ... — anything numpy
            # coerces without making numbers out of it.
            return f"payload dtype {arr.dtype} is not numeric"
        if np.issubdtype(arr.dtype, np.floating) \
                and not bool(np.isfinite(arr).all()):
            return "payload contains NaN/Inf"
        if self.preprocess is None:
            want = self.engine._plan_shape(1)[1:]
            if tuple(arr.shape) != tuple(want):
                return (f"payload shape {tuple(arr.shape)} != engine "
                        f"input {tuple(want)}")
        return None

    def _journal_resolve(self, r: Request) -> None:
        if self.journal is not None and r.jid is not None:
            self.journal.resolve(r.jid, r.outcome, error=r.error)

    def _reject(self, payload: Any, reason: str, now: float,
                deadline_s: float | None,
                jid: int | None = None) -> Request:
        r = Request(payload, deadline_s=deadline_s)
        r.jid = jid
        r.arrival_s = now
        r.resolve("rejected", error=reason)
        self._journal_resolve(r)
        self._metrics.record_rejected()
        self.flight.record(id=r.id, outcome="rejected", error=reason,
                           arrival_s=now, done_s=now, latency_s=0.0)
        _trace.instant("serve.reject", "serve", req=r.id, reason=reason)
        return r

    # ---- request lifecycle ------------------------------------------------
    def submit(self, payload: Any, deadline_s: float | None = None,
               now: float | None = None, jid: int | None = None) -> Request:
        """``jid`` is the journal-replay path (DESIGN.md §14.3): the
        record is already on disk, so the server attaches the identity
        instead of journaling a duplicate submit."""
        # Arrival is stamped from the server's clock so latency samples
        # stay in one clock domain when a fake clock is injected.
        now = self.clock() if now is None else now
        if self.validate:
            err = self._payload_error(payload)
            if err is not None:
                return self._reject(payload, err, now, deadline_s, jid=jid)
        if self.max_queue is not None \
                and len(self.scheduler) >= self.max_queue:
            return self._reject(
                payload, f"queue full ({len(self.scheduler)} >= "
                         f"max_queue={self.max_queue})", now, deadline_s,
                jid=jid)
        if self.journal is not None and jid is None:
            # WAL order: the submit record hits disk before the request
            # enters the scheduler — a crash in between replays it.
            jid = self.journal.submit("bnn", payload)
        r = self.scheduler.submit(payload, deadline_s=deadline_s, now=now)
        r.jid = jid
        _trace.instant("serve.submit", "serve", req=r.id)
        return r

    def poll(self, request: Request) -> bool:
        return request.done

    # ---- failure handling -------------------------------------------------
    def _retry_or_fail(self, r: Request, exc: Exception, now: float,
                       requeue: list[Request]) -> None:
        """One failed attempt for one request: back off and requeue, or
        resolve ``error`` when attempts are exhausted."""
        r.attempts += 1
        max_attempts = self.retry.max_attempts if self.retry else 1
        if r.attempts < max_attempts:
            r.not_before = now + self.retry.backoff_s(r.attempts)
            self._metrics.record_retry()
            _trace.instant("serve.retry", "serve", req=r.id,
                           attempt=r.attempts)
            requeue.append(r)
            return
        r.resolve("error", error=f"{type(exc).__name__}: {exc}")
        self._journal_resolve(r)
        self._metrics.record_error()
        self._errored.append(r)
        self.flight.record(
            id=r.id, outcome="error", error=r.error, attempts=r.attempts,
            arrival_s=r.arrival_s, deadline_s=r.deadline_s, done_s=now,
            latency_s=now - r.arrival_s)
        _trace.instant("serve.error", "serve", req=r.id)

    def _note_demotion(self, now: float, bucket: int) -> None:
        d = self.health.ladder(bucket).demotions[-1]
        self._metrics.record_degraded()
        _obs_metrics.get_registry().event(
            "demotion", server="bnn", **d)
        self.flight.record(kind="demotion", outcome="demoted",
                           from_mode=d["from_mode"], to_mode=d["to_mode"],
                           bucket=bucket, done_s=now)
        _trace.instant("serve.demote", "serve", bucket=bucket,
                       from_mode=d["from_mode"], to_mode=d["to_mode"])

    def _on_batch_failure(self, batch: list[Request], exc: Exception,
                          now: float, mode: str | None,
                          probing: bool, bucket: int) -> None:
        """A whole dispatched/scattered batch failed: update the
        bucket's backend-health ladder (possibly demoting it — other
        buckets are untouched), then retry-or-fail each request."""
        if self.health is not None:
            if probing:
                self.health.probe_failed(bucket, mode, now)
            elif self.health.record_failure(bucket, now) is not None:
                self._note_demotion(now, bucket)
        requeue: list[Request] = []
        for r in batch:
            self._retry_or_fail(r, exc, now, requeue)
        if requeue:
            self.scheduler.requeue(requeue)

    # ---- dispatch / scatter ----------------------------------------------
    def _stage_rows(self, batch: list[Request], payloads: list[Any]
                    ) -> tuple[list[np.ndarray], list[Request],
                               list[int], list[tuple[Request, Exception]]]:
        """Host staging with per-row fault isolation: a payload whose
        conversion/preprocess raises is zero-filled (zeros are inert —
        the same trick bucket padding uses) so the rest of the batch
        still dispatches; its request is returned as a failure."""
        zero_row: np.ndarray | None = None
        rows: list[np.ndarray | None] = []
        kept: list[Request] = []
        row_idx: list[int] = []
        failures: list[tuple[Request, Exception]] = []
        for i, p in enumerate(payloads):
            r = batch[i] if i < len(batch) else None
            try:
                row = np.asarray(p)
                if r is not None and _faults._PLAN is not None:
                    _faults.maybe_fault("server.preprocess", req=r.id)
                if self.preprocess is not None:
                    row = self.preprocess(row)
                rows.append(row)
                if r is not None:
                    kept.append(r)
                    row_idx.append(i)
            except Exception as e:      # noqa: BLE001 — isolate the row
                rows.append(None)
                if r is not None:
                    failures.append((r, e))
        if zero_row is None:
            zero_row = np.zeros(self.engine._plan_shape(1)[1:], np.uint8)
        return ([row if row is not None else zero_row for row in rows],
                kept, row_idx, failures)

    def _dispatch(self, batch: list[Request], payloads: list[Any],
                  mode: str | None = None
                  ) -> tuple[_InFlight | None,
                             list[tuple[Request, Exception]]]:
        t0 = self.clock()
        with _trace.span("serve.stage", "serve", bucket=len(payloads),
                         n_real=len(batch)):
            rows, kept, row_idx, failures = self._stage_rows(batch,
                                                             payloads)
        if not kept:
            return None, failures
        if _faults._PLAN is not None:
            _faults.maybe_fault("server.dispatch", bucket=len(rows),
                                mode=mode or self.engine.matmul_mode,
                                tenant=self.tenant)
        with _trace.span("serve.dispatch", "serve", bucket=len(rows),
                         mode=mode):
            x = self._place(np.stack(rows))
            out = self._executable(len(rows), mode)(x)  # async: returns now
        t1 = self.clock()
        self.dispatched_rows += len(rows)
        self._metrics.mark_dispatch(bucket=len(rows))
        return (_InFlight(kept, row_idx, out, len(rows), t1, t1 - t0,
                          mode), failures)

    def _try_dispatch(self, batch: list[Request], payloads: list[Any],
                      now: float) -> _InFlight | None:
        """Dispatch with the full failure protocol: per-bucket mode
        selection (this bucket's degradation ladder + quarantine
        re-probe), batch-level retry on failure, per-row failure
        resolution."""
        bucket = len(payloads)
        mode, probing = None, False
        if self.health is not None:
            # materialize this bucket's ladder at first dispatch so the
            # per-bucket surface (metrics, snapshot) covers every bucket
            # that actually served, not only the ones that failed
            self.health.ladder(bucket)
            probe = self.health.probe_due(bucket, now)
            mode, probing = ((probe, True) if probe is not None
                             else (self.health.mode_for(bucket), False))
        try:
            flight, failures = self._dispatch(batch, payloads, mode=mode)
        except Exception as e:          # noqa: BLE001 — never kill the loop
            self._on_batch_failure(batch, e, now, mode, probing, bucket)
            return None
        requeue: list[Request] = []
        for r, exc in failures:
            self._retry_or_fail(r, exc, now, requeue)
        if requeue:
            self.scheduler.requeue(requeue)
        # Health verdicts wait for the readback: an async dispatch
        # returning is no proof the executable works, and crediting it
        # here would let interleaved dispatches reset the
        # consecutive-failure count between two readback faults.
        if flight is not None:
            flight.probing = probing
        return flight

    def _readback(self, flight: _InFlight) -> np.ndarray:
        """The one blocking point, optionally watchdog-bounded: a
        stalled executable becomes :class:`WatchdogTimeout` instead of a
        hung serve loop (the stuck thread is daemonized and abandoned —
        its buffer is dropped on the floor, not replayed)."""
        def blocking() -> np.ndarray:
            if _faults._PLAN is not None:
                _faults.maybe_fault("server.device", bucket=flight.bucket,
                                    tenant=self.tenant)
            return np.asarray(flight.out)

        if self.watchdog_s is None:
            return blocking()
        box: dict[str, Any] = {}

        def work():
            try:
                box["out"] = blocking()
            except Exception as e:      # noqa: BLE001 — re-raised below
                box["err"] = e

        th = threading.Thread(target=work, daemon=True)
        th.start()
        th.join(self.watchdog_s)
        if th.is_alive():
            raise WatchdogTimeout(
                f"device readback exceeded watchdog_s={self.watchdog_s}")
        if "err" in box:
            raise box["err"]
        return box["out"]

    def _scatter(self, flight: _InFlight) -> list[Request]:
        with _trace.span("serve.device", "serve", bucket=flight.bucket):
            host = self._readback(flight)   # the only blocking point
        now = self.clock()
        with _trace.span("serve.scatter", "serve",
                         n_real=len(flight.batch)):
            for r, i in zip(flight.batch, flight.row_idx):
                r.resolve("served", host[i])
                self._journal_resolve(r)
        self._metrics.record([now - r.arrival_s for r in flight.batch])
        for r in flight.batch:
            self.flight.record(
                id=r.id, outcome="served", bucket=flight.bucket,
                arrival_s=r.arrival_s, deadline_s=r.deadline_s,
                dispatched_s=flight.t_dispatch, done_s=now,
                queue_s=flight.t_dispatch - r.arrival_s,
                stage_s=flight.stage_s, latency_s=now - r.arrival_s,
                attempts=r.attempts, mode=flight.mode)
        return flight.batch

    def _try_scatter(self, flight: _InFlight,
                     now: float | None = None) -> list[Request]:
        try:
            done = self._scatter(flight)
        except Exception as e:          # noqa: BLE001 — never kill the loop
            now = self.clock() if now is None else now
            self._on_batch_failure(flight.batch, e, now, flight.mode,
                                   probing=flight.probing,
                                   bucket=flight.bucket)
            return []
        if self.health is not None:
            if flight.probing:
                # The quarantined faster mode survived its probe end to
                # end: promote this bucket's ladder back up.
                self.health.promote(flight.bucket, flight.mode)
                _trace.instant("serve.promote", "serve", mode=flight.mode,
                               bucket=flight.bucket)
                self.flight.record(kind="promotion", outcome="promoted",
                                   to_mode=flight.mode,
                                   bucket=flight.bucket,
                                   done_s=self.clock() if now is None
                                   else now)
            else:
                self.health.record_success(flight.bucket)
        return done

    def _record_shed(self, shed: list[Request], now: float) -> None:
        self._metrics.record_dropped(len(shed))
        for r in shed:
            self._journal_resolve(r)
            self.flight.record(id=r.id, outcome="shed",
                               arrival_s=r.arrival_s,
                               deadline_s=r.deadline_s, done_s=now,
                               latency_s=now - r.arrival_s)
            _trace.instant("serve.shed", "serve", req=r.id)

    def step(self, now: float | None = None,
             force: bool = False, dispatch: bool = True) -> list[Request]:
        """One serving tick: dispatch the next batch (policy permitting),
        then scatter the previously in-flight one.  Under async dispatch
        the new batch's device work overlaps the old batch's readback;
        synchronously each batch completes before the next is assembled.
        Returns the requests completed this tick.  Failures never
        escape: a faulted batch re-queues (retry policy) or resolves
        ``error``, and the loop keeps serving.

        ``dispatch=False`` runs the housekeeping half only — shed
        expired requests, scatter the in-flight batch, hand back error
        completions — without assembling a new batch.  A multi-tenant
        arbiter uses it to retire a lane's in-flight work on ticks where
        fair-share admission picked a different lane."""
        now = self.clock() if now is None else now
        # Shed before assembly so the flight recorder sees every deadline
        # outcome (padded_batch sheds too, but silently — same policy,
        # same ``now``, so nothing is left for it to shed).
        shed = self.scheduler.shed_expired(now)
        if shed:
            self._record_shed(shed, now)
        flight = None
        if dispatch:
            with _trace.span("serve.assemble", "serve"):
                got = self.scheduler.padded_batch(now, force=force)
            if got is not None:
                flight = self._try_dispatch(*got, now)
        done: list[Request] = []
        if not self.async_dispatch:
            if flight is not None:
                done = self._try_scatter(flight, now)
        else:
            if self._pending is not None:
                pending, self._pending = self._pending, None
                done = self._try_scatter(pending, now)
            self._pending = flight
        # Error-resolved requests are terminal completions too.
        if self._errored:
            done, self._errored = done + self._errored, []
        return done

    def _abort_wedged(self, now: float) -> list[Request]:
        """Drain's last resort: terminally resolve everything still
        outstanding as ``error`` so no request is left dangling."""
        stuck: list[Request] = []
        if self._pending is not None:
            stuck += self._pending.batch
            self._pending = None
        stuck += self.scheduler.next_batch(now, force=True) or []
        while len(self.scheduler):     # backoff'd stragglers too
            r = self.scheduler._queue.popleft()
            stuck.append(r)
        for r in stuck:
            if r.done:
                continue
            r.resolve("error", error="drain wedged: step budget exhausted")
            self._journal_resolve(r)
            self._metrics.record_error()
            self.flight.record(id=r.id, outcome="error", error=r.error,
                               arrival_s=r.arrival_s, done_s=now,
                               latency_s=now - r.arrival_s)
        return [r for r in stuck if r.outcome == "error"]

    def drain(self, now: float | None = None,
              max_steps: int | None = None) -> list[Request]:
        """Serve until the queue is empty and nothing is in flight
        (skipping the batch-wait policy: drain is a flush).  Returns the
        requests completed during the drain.

        Bounded: at most ``max_steps`` ticks (default: generous for the
        current queue × retry budget), after which anything still
        outstanding resolves ``error`` — a wedged in-flight batch
        surfaces instead of hanging the caller forever.  When every
        queued request is in retry backoff, waits it out through the
        injectable ``sleep`` (a fixed explicit ``now`` cannot advance,
        so backoff under it falls to the step bound)."""
        if max_steps is None:
            budget = self.retry.max_attempts if self.retry else 1
            max_steps = 4 * (len(self.scheduler) + 2) * budget + 16
        done: list[Request] = []
        steps = 0
        while len(self.scheduler) or self._pending is not None:
            if steps >= max_steps:
                done += self._abort_wedged(
                    self.clock() if now is None else now)
                break
            steps += 1
            t = self.clock() if now is None else now
            done += self.step(t, force=True)
            if self._pending is None and len(self.scheduler):
                wait = self.scheduler.backoff_wait(t)
                if wait is not None and wait > 0:
                    self._sleep(wait)
        return done

    # ---- observability ----------------------------------------------------
    @property
    def metrics_registry(self):
        """This server's metric series (``repro.obs.MetricsRegistry``):
        ``serve.latency_s``, ``serve.bucket_size`` (per-bucket dispatch
        histogram), ``serve.served``, ``serve.dropped``, plus the
        resilience counters ``serve.retries`` / ``serve.errors`` /
        ``serve.rejected`` / ``serve.degraded``."""
        return self._metrics.registry

    @property
    def queue_depth(self) -> int:
        inflight = len(self._pending.batch) if self._pending else 0
        return len(self.scheduler) + inflight

    def metrics(self) -> dict:
        """p50/p95 request latency (submit→scatter, ms), served/dropped
        counts, resilience counters (retries/errors/rejected/degraded),
        live queue depth, the current serving mode, and throughput over
        the busy window (first dispatch → last scatter)."""
        extra = {"tenant": self.tenant} if self.tenant is not None else {}
        if self.health is not None and self.health.ladders:
            # Per-bucket ladder state (DESIGN.md §14.3): which buckets
            # are demoted/quarantined, independent of the worst-case
            # ``mode`` reported below.
            extra["bucket_health"] = {
                b: lad.snapshot(self.clock())
                for b, lad in sorted(self.health.ladders.items())}
        if self.pipeline_devices is not None:
            extra["placement"] = {"kind": "pipeline",
                                  "devices": [str(d) for d in
                                              self.pipeline_devices]}
        elif self.placement is not None:
            extra["placement"] = {"kind": "data",
                                  "shards": self.data_parallel}
        return self._metrics.snapshot(
            dropped=self.scheduler.dropped,
            queue_depth=self.queue_depth,
            async_dispatch=self.async_dispatch,
            data_parallel=self.data_parallel,
            mode=(self.health.mode if self.health is not None
                  else self.engine.matmul_mode),
            buckets=list(self.scheduler.buckets), **extra)
