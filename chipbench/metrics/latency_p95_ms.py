"""95th percentile (nearest rank) of due-to-answer latency over every
request due in the window; an unserved request counts as infinite, and an
infinite percentile is not reported."""

from chipbench import stats


def read(run):
    v = stats.percentile(stats.latencies_s(run), 0.95)
    return stats.finite(v * 1e3 if v is not None else None)
