"""Device-busy milliseconds per round inside the traced solves' spans."""


def read(run):
    if run.trace is None or not run.timed:
        return None
    rounds = sum(s.rounds for s in run.timed)
    return 1e3 * run.trace.solve_busy_s / rounds
