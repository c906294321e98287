"""Seconds from process start to the first request of the window: weights
on the device, planning, stage compiles (from the cache after a cell's
first run) and warm-up."""


def read(run):
    return run.setup_s
