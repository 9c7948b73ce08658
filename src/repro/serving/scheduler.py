"""Request batching for serving.

The paper's engine serves one image at a time on a phone; at datacenter
scale the same engine fronts a batch scheduler.  Policy: assemble the
largest batch available up to ``max_batch``, but never hold a request
longer than ``max_wait_s`` (latency/throughput knob).  Batches are padded
to the nearest compiled bucket size so XLA never recompiles at serve time;
padding is **zero-filled** (shaped like the last real payload) and the
padded tail of the results is discarded — pad rows cost device FLOPs but
never replay a real request through a potentially stateful ``run``.

Overload protection: a request may carry a ``deadline_s`` (seconds of
queue residency it will tolerate).  Expired requests are shed — popped
with ``done=True, result=None`` and counted in ``dropped`` — so a queue
growing faster than the engine drains it sheds load instead of growing
without bound.

Every time-dependent method takes an injectable ``now=`` (monotonic
seconds) so policy is testable with a fake clock.
"""

from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Any, Callable

import numpy as np

from repro.obs.trace import clock as _clock


#: The terminal request outcomes (DESIGN.md §11): every submitted
#: request ends ``done=True`` with exactly one of these.
OUTCOMES = ("served", "shed", "error", "rejected")


@dataclasses.dataclass
class Request:
    payload: Any
    arrival_s: float = dataclasses.field(default_factory=_clock)
    deadline_s: float | None = None   # max queue residency; None = patient
    id: int = dataclasses.field(
        default_factory=itertools.count().__next__)
    result: Any = None
    done: bool = False
    # ---- resilience state (DESIGN.md §11) -------------------------------
    outcome: str | None = None        # one of OUTCOMES once done
    error: str | None = None          # terminal failure reason
    attempts: int = 0                 # dispatch tries so far
    not_before: float | None = None   # retry backoff: ineligible until
    # ---- crash safety (DESIGN.md §14) -----------------------------------
    jid: int | None = None            # durable journal id, if journaled

    def expired(self, now: float) -> bool:
        return (self.deadline_s is not None
                and (now - self.arrival_s) >= self.deadline_s)

    def eligible(self, now: float) -> bool:
        """In-backoff requests sit in the queue but skip assembly."""
        return self.not_before is None or now >= self.not_before

    def resolve(self, outcome: str, result: Any = None,
                error: str | None = None) -> "Request":
        assert outcome in OUTCOMES, outcome
        self.result, self.done = result, True
        self.outcome, self.error = outcome, error
        return self


def _zero_like(payload: Any) -> Any:
    """A zero payload with the shape/dtype of a real one (batch padding)."""
    return np.zeros_like(np.asarray(payload))


def shed_expired_requests(queue: "deque[Request]", now: float
                          ) -> tuple["deque[Request]", list[Request]]:
    """Partition a request queue into (kept, shed-by-deadline); shed
    requests are completed with ``result=None``.  The one shed policy —
    used by both the batch scheduler and the LM admission queue."""
    kept: deque[Request] = deque()
    shed: list[Request] = []
    for r in queue:
        if r.expired(now):
            r.resolve("shed")
            shed.append(r)
        else:
            kept.append(r)
    return kept, shed


def buckets_for(max_batch: int,
                ladder: tuple[int, ...] = (1, 2, 4, 8, 16)) -> tuple[int, ...]:
    """The canonical bucket set for a max batch size: the power-of-two
    ladder below it plus ``max_batch`` itself (so the scheduler invariant
    ``buckets[-1] >= max_batch`` holds for any value)."""
    return tuple(sorted({b for b in ladder if b < max_batch} | {max_batch}))


@dataclasses.dataclass
class BatchScheduler:
    max_batch: int = 8
    max_wait_s: float = 0.005
    buckets: tuple[int, ...] = (1, 2, 4, 8)

    def __post_init__(self):
        self._queue: deque[Request] = deque()
        self.dropped = 0          # deadline-shed requests (overload stat)
        assert tuple(sorted(self.buckets)) == tuple(self.buckets)
        assert self.buckets[-1] >= self.max_batch

    def submit(self, payload: Any, deadline_s: float | None = None,
               now: float | None = None) -> Request:
        r = Request(payload, deadline_s=deadline_s)
        if now is not None:
            r.arrival_s = now
        self._queue.append(r)
        return r

    def __len__(self) -> int:
        return len(self._queue)

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    # ---- deadline shedding -----------------------------------------------
    def shed_expired(self, now: float | None = None) -> list[Request]:
        """Pop every expired request (done, result=None); count them."""
        if not self._queue:
            return []
        now = _clock() if now is None else now
        self._queue, shed = shed_expired_requests(self._queue, now)
        self.dropped += len(shed)
        return shed

    # ---- batch assembly ---------------------------------------------------
    def ready(self, now: float | None = None) -> bool:
        if not self._queue:
            return False
        if len(self._queue) >= self.max_batch:
            return True
        now = _clock() if now is None else now
        return (now - self._queue[0].arrival_s) >= self.max_wait_s

    def next_batch(self, now: float | None = None,
                   force: bool = False) -> list[Request] | None:
        """Shed expired requests, then pop up to max_batch *eligible*
        requests if the policy says go (``force=True`` skips the wait
        policy — final flush).  Requests in retry backoff
        (``not_before`` in the future) keep their queue position but are
        passed over until their delay elapses."""
        now = _clock() if now is None else now
        self.shed_expired(now)
        if not (self._queue if force else self.ready(now)):
            return None
        take: list[Request] = []
        keep: deque[Request] = deque()
        for r in self._queue:
            if len(take) < self.max_batch and r.eligible(now):
                take.append(r)
            else:
                keep.append(r)
        if not take:
            return None
        self._queue = keep
        return take

    def requeue(self, requests: list[Request]) -> None:
        """Front-insert failed-batch requests for retry, preserving
        their relative order (they were at the head when popped)."""
        for r in reversed(requests):
            self._queue.appendleft(r)

    def backoff_wait(self, now: float) -> float | None:
        """Seconds until the soonest queued request leaves retry
        backoff, or None when the queue is empty / something is already
        eligible (i.e. only meaningful when assembly is starved purely
        by backoff)."""
        if not self._queue or any(r.eligible(now) for r in self._queue):
            return None
        return min(r.not_before for r in self._queue) - now

    def padded_batch(self, now: float | None = None, force: bool = False
                     ) -> tuple[list[Request], list[Any]] | None:
        """Pop a batch and zero-pad its payloads to the bucket size.

        The single batch-assembly path: every executed payload list is
        exactly a bucket size, and rows past ``len(batch)`` are padding.
        """
        batch = self.next_batch(now, force=force)
        if batch is None:
            return None
        bucket = self.bucket_for(len(batch))
        payloads = [r.payload for r in batch]
        pad = bucket - len(batch)
        if pad:
            payloads = payloads + [_zero_like(payloads[-1])] * pad
        return batch, payloads

    def drain(self, run: Callable[[list[Any]], list[Any]],
              now: float | None = None) -> list[Request]:
        """Assemble, zero-pad to bucket, execute, scatter the real rows.

        ``run`` is always called with exactly a bucket-sized payload list;
        results beyond ``len(batch)`` are padding output and discarded.
        """
        got = self.padded_batch(now)
        if got is None:
            return []
        batch, payloads = got
        results = run(payloads)
        for r, out in zip(batch, results):
            r.resolve("served", out)
        return batch
