"""Plain host references for the benchmark's answers, and their controls.

Independent of the program: numpy and scipy only, never JAX and nothing the
program computed (edge values are derived here from the topology).  The
references run in spawned worker processes, so the process that holds the
chip keeps it and the workers never import JAX.  The graph reaches the
workers once, through shared memory.

* PageRank: float64 power iteration of ``x = (1 - d)/n + A x`` with
  ``A[u, v] = d / deg(v)`` for each edge ``v -> u``, run to an L1 change of
  1e-10.
* SSSP: scipy's Dijkstra from the source; unreachable vertices read the
  engine's infinity ``2**30 - 1``.

The controls are the same references computed one precision lower, as a later
change that narrowed the values would compute them: bfloat16 values with
float32 accumulation (PageRank's state and edge values; SSSP's distances).
"""

from __future__ import annotations

import multiprocessing
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import shared_memory

import numpy as np

#: The engine's min-plus infinity (``repro.core.semiring.INT_INF``).
INT_INF = 2**30 - 1
#: L1 change at which the float64 PageRank reference stops.
REF_PR_TOL = 1e-10

_GRAPH = None  # the worker's view of the shared graph, set by _attach


def round_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even), as float32."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    bits = x.view(np.uint32)
    finite = np.isfinite(x)
    lsb = (bits >> 16) & 1
    rounded = ((bits + np.uint32(0x7FFF) + lsb) & np.uint32(0xFFFF0000)).view(
        np.float32
    )
    return np.where(finite, rounded, x)


def pull_matrix(n, indptr, indices, values, dtype=np.float64):
    """``A[u, v]`` = value of edge ``v -> u`` from the pull CSR."""
    import scipy.sparse as sp

    return sp.csr_matrix((values.astype(dtype), indices, indptr), shape=(n, n))


def pagerank(n, indptr, indices, damping=0.85, control=False, control_tol=1e-4):
    """PageRank of the graph; ``control=True`` computes it in bfloat16.

    The control keeps the state and the edge values in bfloat16 and
    accumulates each row in float32, stopping as the engine does at an L1
    change of ``control_tol`` (or after 1000 rounds, if rounding never lets
    it settle).
    """
    deg = np.bincount(indices, minlength=n).astype(np.float64)
    vals = damping / np.maximum(deg[indices], 1.0)
    if control:
        A = pull_matrix(n, indptr, indices, round_bf16(vals), np.float32)
        teleport = np.float32((1.0 - damping) / n)
        x = round_bf16(np.full(n, 1.0 / n, np.float32))
        for _ in range(1000):
            x_new = round_bf16(teleport + A @ x)
            change = float(np.abs(x_new.astype(np.float64) - x).sum())
            x = x_new
            if change <= control_tol:
                break
        return x.astype(np.float64)
    A = pull_matrix(n, indptr, indices, vals)
    teleport = (1.0 - damping) / n
    x = np.full(n, 1.0 / n)
    for _ in range(10_000):
        x_new = teleport + A @ x
        if np.abs(x_new - x).sum() <= REF_PR_TOL:
            return x_new
        x = x_new
    raise RuntimeError("PageRank reference did not converge")


def sssp(n, indptr, indices, weights, source, control=False, forward=None):
    """Distances from ``source``; ``control=True`` keeps them in bfloat16.

    The reference is Dijkstra over the forward graph (``forward``, when the
    caller has it, is that graph as a scipy matrix).  The control is
    Bellman-Ford whose distances are rounded to bfloat16 after every
    relaxation (float32 adds), run until no distance changes.
    """
    if control:
        return _sssp_bf16(n, indptr, indices, weights, source)
    from scipy.sparse.csgraph import dijkstra

    if forward is None:
        forward = pull_matrix(n, indptr, indices, weights).T.tocsr()
    d = dijkstra(forward, directed=True, indices=int(source))
    return np.where(np.isinf(d), float(INT_INF), d)


def _sssp_bf16(n, indptr, indices, weights, source):
    x = np.full(n, np.inf, np.float32)
    x[source] = 0.0
    w = weights.astype(np.float32)
    has_in = np.diff(indptr) > 0
    starts = indptr[:-1][has_in]
    for _ in range(n + 1):
        best = np.full(n, np.inf, np.float32)
        best[has_in] = np.minimum.reduceat(round_bf16(x[indices] + w), starts)
        x_new = np.minimum(x, best)
        if np.array_equal(x_new, x):
            break
        x = x_new
    return np.where(np.isinf(x), float(INT_INF), x.astype(np.float64))


# --------------------------------------------------------------------------- #
# worker pool over a graph in shared memory
# --------------------------------------------------------------------------- #
def _attach(n, specs):
    global _GRAPH
    blocks, arrays = [], {}
    for name, (shm_name, shape, dtype) in specs.items():
        shm = shared_memory.SharedMemory(name=shm_name)
        blocks.append(shm)
        arrays[name] = np.ndarray(shape, dtype=dtype, buffer=shm.buf)
    _GRAPH = {"n": n, "arrays": arrays, "blocks": blocks, "forward": None}


def _task(kind, damping, source, control):
    n, g = _GRAPH["n"], _GRAPH["arrays"]
    if kind == "pagerank":
        return pagerank(n, g["indptr"], g["indices"], damping, control)
    if kind == "sssp":
        if _GRAPH["forward"] is None and not control:
            _GRAPH["forward"] = pull_matrix(
                n, g["indptr"], g["indices"], g["weights"]
            ).T.tocsr()
        return sssp(
            n, g["indptr"], g["indices"], g["weights"], source, control,
            forward=_GRAPH["forward"],
        )
    raise ValueError(f"unknown reference {kind!r}")


class ReferencePool:
    """Spawned workers that answer reference tasks on one shared graph.

    ``with ReferencePool(graph, workers) as pool: pool.submit("sssp",
    source=s)``.  Leaving the block stops the workers and frees the shared
    memory.
    """

    def __init__(self, graph, workers: int):
        self._blocks = []
        specs = {}
        for name in ("indptr", "indices", "weights"):
            a = np.ascontiguousarray(getattr(graph, name))
            shm = shared_memory.SharedMemory(create=True, size=max(a.nbytes, 1))
            np.ndarray(a.shape, a.dtype, buffer=shm.buf)[...] = a
            self._blocks.append(shm)
            specs[name] = (shm.name, a.shape, a.dtype.str)
        ctx = multiprocessing.get_context("spawn")
        self._pool = ProcessPoolExecutor(
            max_workers=workers,
            mp_context=ctx,
            initializer=_attach,
            initargs=(graph.n, specs),
        )

    def submit(self, kind, *, damping=0.85, source=None, control=False):
        return self._pool.submit(_task, kind, damping, source, control)

    def close(self):
        self._pool.shutdown(wait=True, cancel_futures=True)
        for shm in self._blocks:
            shm.close()
            shm.unlink()
        self._blocks = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
