"""Load generator: 95th percentile of how late each request was sent after
it was due (send time minus due time)."""

from chipbench import stats


def read(run):
    late = sorted(r["sent"] - r["due"] for r in run.requests)
    v = stats.percentile(late, 0.95)
    return v * 1e3 if v is not None else None
