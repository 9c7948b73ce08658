"""Seconds from process start to the first request of the window: building
the weights and the server, compiling and tuning every bucket, warming up."""


def read(run):
    return run.setup_s
