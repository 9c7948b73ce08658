"""Images served, with their answer in hand by the window's end, per second
of the window."""

from chipbench import stats


def read(run):
    return stats.images_in_window(run) / run.seconds
