"""Hub personalized PageRank: the hub rule, a float64 reference and its control.

Independent of the program, as ``bench/reference.py`` is: numpy and scipy
only, never JAX, edge values derived here from the topology.

* Hubs: the ``count`` vertices of highest degree, ties to the lower id.
* Reference: float64 power iteration of ``X = R + A X``, one column per hub,
  with ``A[u, v] = d / deg(v)`` for each edge ``v -> u`` and
  ``R[hub_f, f] = 1 - d``, run until the L1 change of the whole matrix is at
  most 1e-10.
* Control: the same iteration with the state and the edge values in
  bfloat16 and float32 accumulation, stopping as the engine does at a
  whole-matrix L1 change of :data:`CONTROL_TOL` (or after 1000 rounds).

The product ``A X`` is split into row blocks computed on threads (scipy's
sparse product releases the GIL), so one pool worker uses several cores.
:func:`task` runs in a worker of :class:`bench.reference.ReferencePool`,
reads the graph that worker attached and takes its share of the cores.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference

CONTROL_TOL = 1e-4  # the cell's tol: the control stops where the engine does


def hubs(indptr, count: int) -> np.ndarray:
    """The ``count`` highest-degree vertices, ties to the lower id, in that order."""
    deg = np.diff(np.asarray(indptr))
    n = deg.shape[0]
    count = int(count)
    if not 0 < count <= n:
        raise ValueError(f"cannot pick {count} hubs of {n} vertices")
    least = np.partition(deg, n - count)[n - count]
    cand = np.flatnonzero(deg >= least)
    return cand[np.lexsort((cand, -deg[cand]))][:count]


def restart(n: int, hub_ids, damping: float, dtype=np.float64) -> np.ndarray:
    """``(n, F)``: column ``f`` holds ``1 - d`` at ``hub_ids[f]``."""
    hub_ids = np.asarray(hub_ids)
    r = np.zeros((n, hub_ids.shape[0]), dtype)
    r[hub_ids, np.arange(hub_ids.shape[0])] = 1.0 - damping
    return r


def _row_blocks(n, indptr, indices, values, parts):
    """``parts`` CSR row blocks of the pull matrix, sharing its index arrays."""
    import scipy.sparse as sp

    cuts = np.linspace(0, n, parts + 1).astype(np.int64)
    return [
        sp.csr_matrix(
            (values[indptr[a]:indptr[b]], indices[indptr[a]:indptr[b]],
             indptr[a:b + 1] - indptr[a]),
            shape=(b - a, n),
        )
        for a, b in zip(cuts[:-1], cuts[1:])
    ]


def ppr(n, indptr, indices, hub_ids, damping=0.85, control=False, threads=1):
    """``(n, F)`` personalized PageRank toward each hub; see the module."""
    indptr = np.asarray(indptr, np.int64)
    deg = np.bincount(indices, minlength=n).astype(np.float64)
    vals = damping / np.maximum(deg[indices], 1.0)
    threads = max(1, int(threads))
    with ThreadPoolExecutor(threads) as pool:
        if control:
            blocks = _row_blocks(n, indptr, indices,
                                 reference.round_bf16(vals.astype(np.float32)), threads)
            r = restart(n, hub_ids, damping, np.float32)
            x = reference.round_bf16(np.full(r.shape, 1.0 / n, np.float32))
            for _ in range(1000):
                ax = np.vstack(list(pool.map(lambda b: b @ x, blocks)))
                x_new = reference.round_bf16(r + ax)
                change = float(np.abs(x_new.astype(np.float64) - x).sum())
                x = x_new
                if change <= CONTROL_TOL:
                    break
            return x.astype(np.float64)
        blocks = _row_blocks(n, indptr, indices, vals, threads)
        r = restart(n, hub_ids, damping)
        x = np.full(r.shape, 1.0 / n)
        for _ in range(10_000):
            x_new = r + np.vstack(list(pool.map(lambda b: b @ x, blocks)))
            if np.abs(x_new - x).sum() <= reference.REF_PR_TOL:
                return x_new
            x = x_new
    raise RuntimeError("hub PPR reference did not converge")


def task(count, damping, control, workers):
    """:func:`ppr` toward the graph's ``count`` hubs, in one of ``workers`` pool workers."""
    g = reference._GRAPH
    n, a = g["n"], g["arrays"]
    threads = max(1, len(os.sched_getaffinity(0)) // workers)
    return ppr(n, a["indptr"], a["indices"], hubs(a["indptr"], count), damping,
               control, threads=threads)
