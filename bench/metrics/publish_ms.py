"""Device ms per round of the leaf ops in the program scope ``commit.publish``.

Summed inside the traced solves' spans and divided by their rounds, as
``round_ms`` is (``bench.scopes``).
"""

from bench import scopes


def read(run):
    return scopes.scope_ms(run, "commit.publish")
