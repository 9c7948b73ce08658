"""Device, dispatch to readback: median over the window's batches of the
time from a batch's dispatch to its readback's return (flight
``ready_s - dispatched_s``).  Nothing to read from a program that does not
stamp ``ready_s``."""

from chipbench import stats


def read(run):
    spans = {(r["dispatched_s"], r["ready_s"])
             for r in stats.served_records(run) if "ready_s" in r}
    v = stats.percentile(sorted(b - a for a, b in spans), 0.50)
    return v * 1e3 if v is not None else None
