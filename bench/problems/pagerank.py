"""PageRank to the engine's tolerance, from the uniform vector every solve.

Edge values are ``d / deg(src)`` (the program's pull convention).  Each
solve's answer is compared with the float64 reference by its L1 distance.
"""

from __future__ import annotations

import itertools

import numpy as np


def edge_values(graph, traffic):
    deg = np.bincount(graph.indices, minlength=graph.n)
    d = float(traffic["damping"])
    return (d / np.maximum(deg[graph.indices], 1)).astype(np.float32)


def problem(traffic):
    from repro.solve import pagerank_problem

    return pagerank_problem(damping=float(traffic["damping"]), tol=float(traffic["tol"]))


def draws(graph, traffic, seed):
    for _ in itertools.count():
        yield "uniform", np.full(graph.n, 1.0 / graph.n, dtype=np.float32)


def reference(pool, label, traffic, control):
    return pool.submit("pagerank", damping=float(traffic["damping"]), control=control)


def compare(answers, refs, traffic):
    l1 = max(
        float(np.abs(x.astype(np.float64) - ref).sum()) for x, ref in zip(answers, refs)
    )
    return {"l1_to_reference": (l1, traffic["limits"]["l1_to_reference"])}
