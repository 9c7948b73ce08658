"""Arithmetic the metric readers share."""

from __future__ import annotations

import math


def percentile(sorted_vals, p: float) -> float | None:
    """Nearest-rank percentile of an ascending sequence (None when empty):
    the smallest value with at least ``p`` of the sample at or below it."""
    n = len(sorted_vals)
    if not n:
        return None
    return sorted_vals[max(0, min(n - 1, math.ceil(p * n) - 1))]


def latencies_s(run) -> list[float]:
    """Due-to-answer seconds of every request of the window, ascending; a
    request that was not served counts as infinite."""
    return sorted(r["done"] - r["due"] if r.get("outcome") == "served"
                  else math.inf for r in run.requests)


def served_records(run) -> list[dict]:
    return [r for r in run.flight if r.get("outcome") == "served"]


def batches(run) -> dict:
    """The window's dispatched batches, keyed by dispatch time and bucket,
    with their staging seconds."""
    out = {}
    for r in served_records(run):
        out[(r["dispatched_s"], r["bucket"])] = r["stage_s"]
    return out


def images_in_window(run) -> int:
    """Requests served with their answer in hand by the window's end."""
    return sum(1 for r in run.requests if r.get("outcome") == "served"
               and r["done"] <= run.t1)


def finite(v: float) -> float | None:
    return v if v is not None and math.isfinite(v) else None
