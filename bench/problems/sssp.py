"""Bellman-Ford SSSP: GAP integer lengths, a new source per solve.

Sources are drawn from the seed, uniformly over vertices with edges, as GAP's
trials pick them.  Distances are int32 and compared exactly.
"""

from __future__ import annotations

import itertools

import numpy as np

from bench.reference import INT_INF


def edge_values(graph, traffic):
    return graph.weights


def problem(traffic):
    from repro.solve import sssp_problem

    return sssp_problem()


def draws(graph, traffic, seed):
    rng = np.random.default_rng([int(seed), 1])
    candidates = np.flatnonzero(graph.degree > 0)
    for _ in itertools.count():
        source = int(candidates[rng.integers(candidates.shape[0])])
        x0 = np.full(graph.n, INT_INF, dtype=np.int32)
        x0[source] = 0
        yield source, x0


def reference(pool, label, traffic, control):
    return pool.submit("sssp", source=label, control=control)


def compare(answers, refs, traffic):
    wrong = sum(
        int(np.count_nonzero(x.astype(np.float64) != ref))
        for x, ref in zip(answers, refs)
    )
    return {"wrong_distances": (wrong, traffic["limits"]["wrong_distances"])}
