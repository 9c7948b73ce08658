"""Scheduler: median of the flight recorder's ``queue_s`` (submit to
dispatch) over the requests served in the window."""

from chipbench import stats


def read(run):
    q = sorted(r["queue_s"] for r in stats.served_records(run))
    v = stats.percentile(q, 0.50)
    return v * 1e3 if v is not None else None
