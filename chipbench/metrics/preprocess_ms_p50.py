"""Preprocess hook: median of the flight recorder's per-request
``preprocess_s`` (the hook alone, inside staging) over the requests served
in the window.  Nothing to read without a hook, or from a program that
does not stamp it."""

from chipbench import stats


def read(run):
    v = stats.percentile(sorted(r["preprocess_s"]
                                for r in stats.served_records(run)
                                if "preprocess_s" in r), 0.50)
    return v * 1e3 if v is not None else None
