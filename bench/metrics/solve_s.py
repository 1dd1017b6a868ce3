"""Seconds per solve: the timed window's span over the solves it holds.

The span runs from the start of the first timed solve to the end of the
last, which is the solve still running when ``--seconds`` had passed; host
time between solves is inside it.
"""


def read(run):
    if not run.timed:
        return None
    return run.window_s / len(run.timed)
