"""Milliseconds per solve inside the program's ``repro.solve`` span with no device op.

Upload, dispatch and fetch: the span's length less the device busy time
inside it, the median over the traced solves (``bench.scopes``).
"""

from bench import scopes


def read(run):
    return scopes.solve_host_ms(run)
