"""Hub personalized PageRank: one ``(n, F)`` solve computes F hub vectors.

The hubs are the graph's F highest-degree vertices (``bench.hub_reference``);
column ``f`` restarts at hub ``f`` with mass ``1 - d``.  Edge values are
``d / deg(src)``, as in ``pagerank``.  Every solve starts from ``1/n`` in each
column and stops when the L1 change of the whole matrix is at most ``tol``.
Each answer is compared with the float64 reference by its L1 distance summed
over the columns.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from bench import hub_reference
from bench.problems import pagerank


def edge_values(graph, traffic):
    return pagerank.edge_values(graph, traffic)


def problem(traffic):
    from repro.solve import rwr_embedding_problem, rwr_restart

    columns, damping = int(traffic["columns"]), float(traffic["damping"])
    return dataclasses.replace(
        rwr_embedding_problem(feature_dim=columns, damping=damping,
                              tol=float(traffic["tol"])),
        default_query=lambda g: rwr_restart(
            g, hub_reference.hubs(g.indptr, columns), damping),
    )


def draws(graph, traffic, seed):
    x0 = np.full((graph.n, int(traffic["columns"])), 1.0 / graph.n, dtype=np.float32)
    for _ in itertools.count():
        yield "hubs", x0


def reference(pool, label, traffic, control):
    # ReferencePool.submit knows only its own tasks; this one reads the
    # graph the pool's workers attached and shares the cores among them.
    return pool._pool.submit(
        hub_reference.task, int(traffic["columns"]), float(traffic["damping"]),
        control, pool._pool._max_workers,
    )


def compare(answers, refs, traffic):
    l1 = max(
        float(np.abs(x.astype(np.float64) - ref).sum()) for x, ref in zip(answers, refs)
    )
    return {"l1_sum_to_reference": (l1, traffic["limits"]["l1_sum_to_reference"])}
