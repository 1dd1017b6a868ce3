"""Seconds from process start to the end of the warm-up solve."""


def read(run):
    return run.setup_s
