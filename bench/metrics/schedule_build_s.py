"""Host seconds of the first ``Solver.schedule(δ)``: stripe build and placement."""


def read(run):
    return run.schedule_build_s
