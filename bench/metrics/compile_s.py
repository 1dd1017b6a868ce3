"""The solver's own compile clock after warm-up (``stats["compile_time_s"]``)."""


def read(run):
    return run.compile_s
