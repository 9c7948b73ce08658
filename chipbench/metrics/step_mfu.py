"""Whole step: model operations per image (2 per MAC, binary MACs at the
int8 peak, float MACs at the bf16 peak) times images served per second of
the window, over the chips' peak, in percent."""

from chipbench import costs, stats


def read(run):
    if run.peak is None:
        return None
    images = stats.images_in_window(run)
    if not images:
        return None
    return 100.0 * images / run.seconds * costs.peak_seconds(run.costs, run.peak) / run.chips
