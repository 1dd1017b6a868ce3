"""Rounds to the fixed point (``EngineResult.rounds``), mean over traced solves."""


def read(run):
    if run.trace is None or not run.timed:
        return None
    return sum(s.rounds for s in run.timed) / len(run.timed)
