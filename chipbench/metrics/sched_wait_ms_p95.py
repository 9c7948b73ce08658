"""Scheduler: 95th percentile of how long a served request waited before
its batch left the scheduler (flight ``assembled_s - arrival_s``).
Nothing to read from a program that does not stamp ``assembled_s``."""

from chipbench import stats


def read(run):
    v = stats.percentile(sorted(r["assembled_s"] - r["arrival_s"]
                                for r in stats.served_records(run)
                                if "assembled_s" in r), 0.95)
    return v * 1e3 if v is not None else None
