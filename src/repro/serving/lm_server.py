"""Continuous-batching LM decode server.

Serving loop tying the pieces together: submitted prompts queue as
:class:`Request` objects, the KVCacheManager assigns cache slots, prefill
fills a slot, and one jitted decode step advances *all* active slots each
tick (continuous batching — new sequences join between ticks, finished
ones free their slot without stalling the rest).

The server speaks the same protocol as the BNN
:class:`~repro.serving.server.InferenceServer` (DESIGN.md §7):
``submit(prompt)`` → Request, ``poll``, ``step``, ``drain`` and
``metrics()`` with the same p50/p95/served/dropped/queue-depth
definitions (latency here is submit → last token).  Deadline-carrying
requests that expire while waiting for a KV slot are shed at admission
and counted in ``dropped``.

Resilience (DESIGN.md §11): the LM server speaks the same terminal-
outcome protocol as the BNN server — every submitted request ends
``done=True`` with ``outcome`` ∈ {served, shed, error, rejected}.
Invalid prompts and queue-full submits resolve ``rejected`` (structured,
at the protocol edge) instead of raising; a faulted decode tick retries
under the shared :class:`RetryPolicy`; ``drain`` is iteration-bounded.

Crash safety (DESIGN.md §14): with ``checkpoint_every=N`` the server
takes consistent-cut KV checkpoints — every active sequence snapshotted
to host at one global position — every N decode ticks *and* after each
admission batch (admissions break the pure-decode window the replay
math needs).  When the decode retry budget is exhausted, instead of
erroring the in-flight sequences it rebuilds the cache from the last
cut into fresh slots and lockstep-replays the ≤N uncheckpointed tokens
(bit-exact — §14.2), bounded by ``max_restore_attempts``; with an
``evacuate`` hook installed (a replica group), exhausted restores hand
the sequences to a healthy lane instead of erroring.  A
:class:`~repro.serving.recovery.RequestJournal` makes accepted submits
durable across hard crashes.

Simplifications vs a production server (recorded in DESIGN.md): one global
position per tick (slot positions are tracked but the decode step uses the
max — correct because attention masks by per-slot validity), greedy
sampling, single-host loop.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import Rules
from repro.models import transformer
from repro.obs import FlightRecorder
from repro.obs import trace as _trace
from repro.obs.metrics import ServingMetrics
from repro.serving import faults as _faults
from repro.serving.faults import RetryPolicy
from repro.serving.kv_cache import KVCacheManager
from repro.serving.recovery import CheckpointSet, KVCheckpointer
from repro.serving.scheduler import Request, shed_expired_requests


@dataclasses.dataclass
class LMServer:
    cfg: transformer.LMConfig
    rules: Rules
    params: Any
    n_slots: int
    max_seq: int
    eos_id: int | None = None
    clock: Callable[[], float] = _trace.clock
    retry: RetryPolicy | None = dataclasses.field(
        default_factory=RetryPolicy)
    max_queue: int | None = None
    flight_capacity: int = 256
    tenant: str | None = None
    # ---- crash safety (DESIGN.md §14) -----------------------------------
    # Consistent-cut checkpoint cadence in decode ticks; None disables
    # checkpoint/restore (a decode fault errors the in-flight batch, the
    # pre-§14 behavior).  The replay bound after a fault is ≤ N tokens.
    checkpoint_every: int | None = None
    max_restore_attempts: int = 2
    journal: Any = None               # recovery.RequestJournal | None
    # Migration hook (set by LMReplicaGroup): called with the in-flight
    # [(Request, Sequence)] when restore attempts are exhausted; True
    # means another lane adopted them all.
    evacuate: Callable[[list], bool] | None = None

    def __post_init__(self):
        self.cache = transformer.init_cache(self.cfg, self.n_slots,
                                            self.max_seq)
        self.manager = KVCacheManager(self.n_slots, self.max_seq)
        self.tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        self.pos = 0
        self._decode = jax.jit(transformer.make_decode_step(
            self.cfg, self.rules, self.max_seq))
        # Single-sequence prefill at a fixed bucket keeps one compilation.
        self._fwd = jax.jit(
            lambda p, t: transformer.forward(p, t, self.cfg, self.rules))
        # ---- server-protocol state (submit/poll/drain/metrics) ----------
        self._waiting: deque[Request] = deque()
        self._by_seq: dict[int, tuple[Request, Any]] = {}
        self._metrics = ServingMetrics(self.clock)
        self.dropped = 0
        self.flight = FlightRecorder(
            self.flight_capacity,
            tags={"tenant": self.tenant} if self.tenant is not None
            else None)
        self._tick_failures = 0   # consecutive faulted decode ticks
        # ---- recovery state (DESIGN.md §14) -----------------------------
        self.checkpointer = KVCheckpointer()
        self._ticks_since_ckpt = 0
        self._restore_attempts = 0  # consecutive restores without a
        #                             clean tick in between
        self.restores = 0
        self.evacuations = 0

    # ---- admission -------------------------------------------------------
    def add_prompt(self, prompt: list[int], max_new: int = 32):
        """Prefill a prompt token-by-token into a slot (compilation-free
        path: reuses the decode step; a bucketed prefill step is the
        optimization the prefill_32k cell lowers)."""
        seq = self.manager.admit(len(prompt), max_new, prompt=prompt)
        for i, tok in enumerate(prompt):
            toks = self.tokens.at[seq.slot, 0].set(tok)
            logits, self.cache = self._decode(
                self.params, self.cache, toks, jnp.int32(self.pos + i))
        self.pos += len(prompt)
        nxt = int(jnp.argmax(logits[seq.slot]))
        # First generated token goes through the manager so ``generated``
        # counts it — a max_new=1 sequence finishes right here.
        self.manager.record_token(seq.seq_id, nxt, self.eos_id)
        self.tokens = self.tokens.at[seq.slot, 0].set(nxt)
        return seq

    # ---- decode tick ---------------------------------------------------------
    def step(self) -> dict[int, int]:
        """One decode tick for all active sequences.  Returns
        {seq_id: new_token} for sequences still active."""
        if not self.manager.active:
            return {}
        if _faults._PLAN is not None:
            _faults.maybe_fault("lm.step", active=len(self.manager.active),
                                pos=self.pos, tenant=self.tenant)
        logits, self.cache = self._decode(
            self.params, self.cache, self.tokens, jnp.int32(self.pos))
        self.pos += 1
        out: dict[int, int] = {}
        next_tokens = np.asarray(jnp.argmax(logits, axis=-1))
        for seq_id, seq in list(self.manager.active.items()):
            tok = int(next_tokens[seq.slot])
            out[seq_id] = tok
            self.manager.record_token(seq_id, tok, self.eos_id)
            self.tokens = self.tokens.at[seq.slot, 0].set(tok)
        return out

    # ---- server protocol (same surface as InferenceServer) ---------------
    def _journal_resolve(self, r: Request) -> None:
        if self.journal is not None and r.jid is not None:
            self.journal.resolve(r.jid, r.outcome, error=r.error)

    def submit(self, prompt: list[int], max_new: int = 16,
               deadline_s: float | None = None,
               now: float | None = None, jid: int | None = None) -> Request:
        """Queue a prompt; it joins the continuous batch when a KV slot
        frees.  ``request.result`` becomes the generated token list.
        Invalid requests are rejected here, at the protocol edge — with
        a structured ``rejected`` outcome (same protocol as the BNN
        server, DESIGN.md §11.2): raising inside drain() would strand
        every other queued request, and raising here would force every
        caller to wrap submit.  ``jid`` is the journal-replay path
        (§14.3): the submit record is already on disk, so the journaled
        identity is attached instead of re-journaled."""
        now = self.clock() if now is None else now
        prompt = list(prompt)
        err = None
        if not prompt:
            err = "empty prompt"
        elif any(not isinstance(t, (int, np.integer)) for t in prompt):
            err = "prompt tokens must be ints"
        elif len(prompt) + max_new > self.max_seq:
            err = (f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                   f"max_seq ({self.max_seq})")
        elif self.max_queue is not None \
                and len(self._waiting) >= self.max_queue:
            err = (f"queue full ({len(self._waiting)} >= "
                   f"max_queue={self.max_queue})")
        r = Request((prompt, max_new), deadline_s=deadline_s)
        r.jid = jid
        # one clock domain for arrival and completion (fake-clock tests)
        r.arrival_s = now
        if err is not None:
            r.resolve("rejected", error=err)
            self._journal_resolve(r)
            self._metrics.record_rejected()
            self.flight.record(id=r.id, outcome="rejected", error=err,
                               arrival_s=now, deadline_s=deadline_s,
                               done_s=now, latency_s=0.0)
            _trace.instant("serve.reject", "serve", req=r.id, reason=err)
            return r
        if self.journal is not None and jid is None:
            # WAL order: the submit record hits disk before the request
            # joins the queue — a crash in between replays it.
            r.jid = self.journal.submit("lm", (prompt, max_new))
        self._waiting.append(r)
        _trace.instant("serve.submit", "serve", req=r.id)
        return r

    def poll(self, request: Request) -> bool:
        return request.done

    def _admit_waiting(self, now: float | None = None) -> None:
        now = self.clock() if now is None else now
        # Shed expired requests anywhere in the queue — a full KV cache
        # must not protect queued requests from their deadlines.
        self._waiting, shed = shed_expired_requests(self._waiting, now)
        self.dropped += len(shed)
        self._metrics.record_dropped(len(shed))
        for r in shed:
            self._journal_resolve(r)
            self.flight.record(id=r.id, outcome="shed",
                               arrival_s=r.arrival_s,
                               deadline_s=r.deadline_s, done_s=now,
                               latency_s=now - r.arrival_s)
        admitted = 0
        while self._waiting and self.manager.can_admit():
            r = self._waiting.popleft()
            prompt, max_new = r.payload
            self._metrics.mark_dispatch()
            seq = self.add_prompt(prompt, max_new=max_new)
            self._by_seq[seq.seq_id] = (r, seq)
            admitted += 1
        if admitted and self.checkpoint_every is not None:
            # Admissions advance ``pos`` through prefill, breaking the
            # pure-decode window the replay math needs — re-cut here
            # (§14.2).  If nothing survived admission (max_new=1
            # finishing in prefill), the stale cut is merely dropped.
            if self.manager.active:
                self._take_checkpoint("admission")
            else:
                self.checkpointer.invalidate()

    def _fail_inflight(self, exc: Exception, now: float) -> list[Request]:
        """Recovery exhausted (or disabled): resolve every in-flight
        sequence ``error`` and release its KV slot so waiting prompts
        can still admit (the decode fault poisons the shared cache
        state for the sequences that were mid-flight, not the
        server)."""
        failed: list[Request] = []
        for seq_id, (r, seq) in list(self._by_seq.items()):
            r.resolve("error", error=f"{type(exc).__name__}: {exc}")
            self._journal_resolve(r)
            self._metrics.record_error()
            self.flight.record(id=r.id, outcome="error", error=r.error,
                               arrival_s=r.arrival_s,
                               deadline_s=r.deadline_s, done_s=now,
                               latency_s=now - r.arrival_s,
                               n_tokens=len(seq.tokens))
            if seq_id in self.manager.active:
                self.manager.release(seq_id)
            del self._by_seq[seq_id]
            failed.append(r)
        self.checkpointer.invalidate()
        _trace.instant("serve.error", "serve", n=len(failed))
        return failed

    # ---- checkpoint / restore (DESIGN.md §14.2) ---------------------------
    def _take_checkpoint(self, reason: str) -> None:
        """Snapshot a consistent cut.  Snapshot-fault policy: a faulted
        *cadence* snapshot keeps the previous cut (still consistent —
        the replay bound just grows, and the next tick retries); a
        faulted *admission*/*restore* snapshot invalidates it (the old
        cut predates a prefill or refers to pre-restore sequence ids)."""
        try:
            self.checkpointer.take(self.cache, self.manager, self.tokens,
                                   self.pos, reason=reason)
        except Exception as e:          # noqa: BLE001 — kv.snapshot site
            if reason != "cadence":
                self.checkpointer.invalidate()
            _trace.instant("serve.ckpt_failed", "serve", reason=reason,
                           error=f"{type(e).__name__}: {e}")
            return
        self._ticks_since_ckpt = 0
        _trace.instant("serve.ckpt", "serve", pos=self.pos,
                       seqs=len(self.manager.active), reason=reason)

    def _restore(self, ck: CheckpointSet) -> int:
        """Rebuild the decode state from the last consistent cut and
        lockstep-replay the uncheckpointed ticks.  Bit-exact (§14.2):
        attention reads only the owning slot's pages, so restored
        sequences may land in fresh slots; between cuts only pure
        decode ticks ran, so every surviving sequence has exactly
        ``m = pos − ck.pos`` known uncheckpointed tokens, and
        force-feeding them reproduces every K/V write verbatim.
        Returns ``m``.  Raises (state untouched) if the ``kv.restore``
        fault site fires or the cut is unusable."""
        if _faults._PLAN is not None:
            _faults.maybe_fault("kv.restore", pos=ck.pos,
                                active=len(self._by_seq),
                                tenant=self.tenant)
        m = self.pos - ck.pos
        for seq_id in self._by_seq:
            if seq_id not in ck.seqs:
                # Admission re-cuts should make this impossible; an
                # unusable cut burns a restore attempt, not the batch.
                raise RuntimeError(f"sequence {seq_id} missing from cut "
                                   f"@pos={ck.pos}")
        cache = transformer.init_cache(self.cfg, self.n_slots,
                                       self.max_seq)
        manager = KVCacheManager(self.n_slots, self.max_seq)
        tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        remapped: dict[int, tuple[Request, Any]] = {}
        replay: list[tuple[Any, list]] = []
        for seq_id, (r, old_seq) in self._by_seq.items():
            c = ck.seqs[seq_id]
            extra = old_seq.tokens[c.generated:]
            assert len(extra) == m, (len(extra), m)
            new_seq = manager.adopt(old_seq.length, old_seq.max_new,
                                    old_seq.generated,
                                    list(old_seq.tokens),
                                    prompt=old_seq.prompt)
            k_host, v_host = c.materialize()
            cache["k"] = cache["k"].at[:, new_seq.slot].set(
                jnp.asarray(k_host))
            cache["v"] = cache["v"].at[:, new_seq.slot].set(
                jnp.asarray(v_host))
            tokens = tokens.at[new_seq.slot, 0].set(c.register)
            remapped[new_seq.seq_id] = (r, new_seq)
            replay.append((new_seq, extra))
        # Install the rebuilt cut, then force-fed lockstep replay: tick
        # i writes the register K/V at pos and loads the token the
        # original tick generated (logits are discarded — the outcome
        # is already known and must not be resampled).
        self.cache, self.manager, self.tokens = cache, manager, tokens
        self.pos = ck.pos
        self._by_seq = remapped
        for i in range(m):
            _, self.cache = self._decode(self.params, self.cache,
                                         self.tokens, jnp.int32(self.pos))
            self.pos += 1
            for new_seq, extra in replay:
                self.tokens = self.tokens.at[new_seq.slot, 0].set(
                    extra[i])
        # The restored state is itself a consistent cut — re-cut so a
        # repeated fault replays from here, not from the stale set
        # (whose sequence ids no longer exist).
        self._take_checkpoint("restore")
        return m

    def _evacuate_inflight(self, now: float) -> bool:
        """Hand the in-flight sequences to the migration hook (a
        replica group adopts them on a healthy lane, §14.4).  All-or-
        nothing: True means the adopter now owns the requests and this
        lane forgets them un-resolved; False falls back to the error
        outcome."""
        items = [(r, seq) for _sid, (r, seq) in self._by_seq.items()]
        try:
            ok = bool(self.evacuate(items))
        except Exception:               # noqa: BLE001 — hook must not kill
            ok = False
        if not ok:
            return False
        for seq_id in list(self._by_seq):
            if seq_id in self.manager.active:
                self.manager.release(seq_id)
        self._by_seq.clear()
        self.checkpointer.invalidate()
        self.evacuations += 1
        self.flight.record(kind="evacuation", outcome="evacuated",
                           seqs=len(items), done_s=now)
        _trace.instant("serve.evacuate", "serve", n=len(items))
        return True

    def _recover(self, exc: Exception, now: float) -> list[Request]:
        """Decode retry budget exhausted: restore from the last cut
        (bounded attempts), else migrate via ``evacuate``, else resolve
        the in-flight sequences ``error`` (the pre-§14 outcome)."""
        while self.checkpoint_every is not None and self._by_seq \
                and self.checkpointer.set is not None \
                and self._restore_attempts < self.max_restore_attempts:
            self._restore_attempts += 1
            try:
                replayed = self._restore(self.checkpointer.set)
            except Exception as re:     # noqa: BLE001 — kv.restore site
                self.flight.record(kind="restore",
                                   outcome="restore_failed",
                                   error=f"{type(re).__name__}: {re}",
                                   attempt=self._restore_attempts,
                                   done_s=now)
                _trace.instant("serve.restore_failed", "serve",
                               attempt=self._restore_attempts)
                continue
            self.restores += 1
            self.flight.record(kind="restore", outcome="restored",
                               pos=self.pos, replayed=replayed,
                               seqs=len(self._by_seq),
                               attempt=self._restore_attempts, done_s=now)
            _trace.instant("serve.restore", "serve", pos=self.pos,
                           replayed=replayed)
            return []
        if self.evacuate is not None and self._by_seq \
                and self._evacuate_inflight(now):
            return []
        return self._fail_inflight(exc, now)

    def serve_tick(self, now: float | None = None) -> list[Request]:
        """One serving tick: admit waiting prompts into free slots, run a
        decode step, complete any sequences that finished.  A faulted
        decode tick never escapes: it retries (up to
        ``retry.max_attempts`` consecutive faults) and then either
        restores from the last KV checkpoint (§14.2) or resolves the
        in-flight sequences ``error`` (DESIGN.md §11.2)."""
        self._admit_waiting(now)
        done: list[Request] = []
        try:
            self.step()
            self._tick_failures = 0
            self._restore_attempts = 0
            if self.checkpoint_every is not None and self.manager.active:
                self._ticks_since_ckpt += 1
                if self._ticks_since_ckpt >= self.checkpoint_every:
                    self._take_checkpoint("cadence")
        except Exception as e:          # noqa: BLE001 — never kill the loop
            self._tick_failures += 1
            budget = self.retry.max_attempts if self.retry else 1
            t = self.clock() if now is None else now
            if self._tick_failures >= budget:
                self._tick_failures = 0
                done += self._recover(e, t)
            else:
                self._metrics.record_retry()
                _trace.instant("serve.retry", "serve",
                               attempt=self._tick_failures)
        now = self.clock() if now is None else now
        for seq_id, (r, seq) in list(self._by_seq.items()):
            if seq_id not in self.manager.active:    # finished + released
                r.resolve("served", list(seq.tokens))
                self._journal_resolve(r)
                self._metrics.record([now - r.arrival_s])
                self.flight.record(
                    id=r.id, outcome="served", arrival_s=r.arrival_s,
                    deadline_s=r.deadline_s, done_s=now,
                    latency_s=now - r.arrival_s, n_tokens=len(seq.tokens))
                del self._by_seq[seq_id]
                done.append(r)
        return done

    # ---- migration (DESIGN.md §14.4) --------------------------------------
    def adopt_sequence(self, request: Request, prompt: list[int],
                       tokens: list[int], max_new: int):
        """Adopt a sequence evacuated from another lane: replay-prefill
        its prompt plus already-generated tokens into a fresh slot
        *here*, register the last generated token, and resume decoding.
        Prefix-preserving, not bit-exact across lanes (RoPE positions
        and cache history differ between lanes), so the already-emitted
        prefix is kept verbatim and only future tokens are computed on
        this lane."""
        assert tokens, "adopted sequence must have generated tokens"
        seq = self.manager.adopt(len(prompt) + len(tokens), max_new,
                                 len(tokens), list(tokens),
                                 prompt=list(prompt))
        feed = list(prompt) + list(tokens[:-1])
        for i, tok in enumerate(feed):
            toks = self.tokens.at[seq.slot, 0].set(tok)
            _, self.cache = self._decode(self.params, self.cache, toks,
                                         jnp.int32(self.pos + i))
        self.pos += len(feed)
        self.tokens = self.tokens.at[seq.slot, 0].set(tokens[-1])
        self._by_seq[seq.seq_id] = (request, seq)
        self._metrics.mark_dispatch()
        # Adoption is an admission event: it advances ``pos`` through
        # the replay prefill, so the lane must re-cut.
        if self.checkpoint_every is not None:
            self._take_checkpoint("admission")
        return seq

    def drain(self, now: float | None = None,
              max_steps: int | None = None) -> list[Request]:
        """Serve until every submitted prompt has completed (or shed).

        Bounded (DESIGN.md §11.2): at most ``max_steps`` ticks — default
        generous for the outstanding work (each sequence needs at most
        ``max_seq`` decode ticks, plus the retry budget) — after which
        anything still outstanding resolves ``error`` instead of
        hanging the caller on a wedged batch."""
        if max_steps is None:
            budget = self.retry.max_attempts if self.retry else 1
            outstanding = len(self._waiting) + len(self._by_seq) + 1
            max_steps = outstanding * (self.max_seq + budget) * 2 + 16
        done: list[Request] = []
        steps = 0
        while self._waiting or self._by_seq:
            if steps >= max_steps:
                t = self.clock() if now is None else now
                wedged = list(self._waiting)
                self._waiting.clear()
                for r in wedged:
                    r.resolve("error",
                              error="drain wedged: step budget exhausted")
                    self._journal_resolve(r)
                    self._metrics.record_error()
                    self.flight.record(
                        id=r.id, outcome="error", error=r.error,
                        arrival_s=r.arrival_s, deadline_s=r.deadline_s,
                        done_s=t, latency_s=t - r.arrival_s)
                _trace.instant("serve.drain_wedged", "serve",
                               n=len(wedged) + len(self._by_seq))
                done += wedged
                done += self._fail_inflight(
                    RuntimeError("drain wedged: step budget exhausted"), t)
                break
            steps += 1
            done += self.serve_tick(now)
        return done

    @property
    def metrics_registry(self):
        """This server's metric series (same shape as InferenceServer's)."""
        return self._metrics.registry

    @property
    def queue_depth(self) -> int:
        return len(self._waiting) + len(self._by_seq)

    def metrics(self) -> dict:
        """Same definitions as InferenceServer (§7.4); latency is submit →
        last token."""
        extra: dict = {}
        if self.tenant is not None:
            extra["tenant"] = self.tenant
        if self.checkpoint_every is not None:
            extra["recovery"] = {
                "checkpoint_every": self.checkpoint_every,
                "restores": self.restores,
                "evacuations": self.evacuations,
                **self.checkpointer.snapshot(),
            }
        return self._metrics.snapshot(
            dropped=self.dropped,
            queue_depth=self.queue_depth,
            kv_utilization=self.manager.utilization, **extra)

    def generate(self, prompt: list[int], max_new: int = 16) -> list[int]:
        """Convenience: run one sequence to completion."""
        seq = self.manager.admit(len(prompt), max_new)
        sid = seq.slot
        out: list[int] = []
        tok = prompt[0]
        for i, tok in enumerate(prompt):
            toks = self.tokens.at[sid, 0].set(tok)
            logits, self.cache = self._decode(
                self.params, self.cache, toks, jnp.int32(self.pos))
            self.pos += 1
        for _ in range(max_new):
            nxt = int(jnp.argmax(logits[sid]))
            out.append(nxt)
            toks = self.tokens.at[sid, 0].set(nxt)
            logits, self.cache = self._decode(
                self.params, self.cache, toks, jnp.int32(self.pos))
            self.pos += 1
            if self.eos_id is not None and nxt == self.eos_id:
                break
        if seq.seq_id in self.manager.active:
            self.manager.release(seq.seq_id)
        return out
