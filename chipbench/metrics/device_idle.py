"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window), in percent."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
