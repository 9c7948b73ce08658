"""Kernels: the least time the chip could take for the model's work on the
images served in the traced window (``costs.least_seconds``: per layer the
larger of operations over the peak and minimal bytes over HBM bandwidth,
weights once per batch), over the device's busy time, in percent.  The
numerator is the same whichever backend serves a layer."""

from chipbench import costs, stats


def read(run):
    if run.trace is None or run.peak is None or run.trace["busy_s"] <= 0:
        return None
    served = len(stats.served_records(run))
    if not served:
        return None
    least = costs.least_seconds(run.costs, run.peak, images=served,
                                calls=len(stats.batches(run)))
    return 100.0 * least / run.trace["busy_s"]
