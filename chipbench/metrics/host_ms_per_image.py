"""Staging and dispatch: host seconds the server spent staging batches
(converting payloads, running the preprocess hook, zero-filling pads), per
image served, from the flight recorder's per-batch ``stage_s``."""

from chipbench import stats


def read(run):
    n = len(stats.served_records(run))
    if not n:
        return None
    return sum(stats.batches(run).values()) / n * 1e3
