"""The commit step's sorted segment-⊕ and the schedule data it reads.

* every cell's ``dst_local`` is non-decreasing and ``row_last`` agrees with
  ``indptr``: as built, after ``Solver.apply_updates``, and through the
  persistent store (whole schedules and per-worker stripes);
* a round through :func:`repro.core.semiring.sorted_segment_reduce` matches a
  round through XLA's segment scatter — bit for bit under min-plus, to
  rounding under plus-times — on a graph with a hub row, empty rows, an empty
  cell and padded rows, for vector and matrix frontiers and a vmapped batch;
* a patch whose row outgrows ``2**passes`` drops the schedule for a rebuild;
* ``scan_passes`` and ``longest_row`` are recorded on the build span and in
  ``Solver.stats``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core.engine import make_schedule, round_fn
from repro.core.semiring import INT_INF, MIN_PLUS, PLUS_TIMES
from repro.graphs.formats import CSRGraph, longest_row, scan_passes
from repro.graphs.generators import make_graph
from repro.graphs.updates import EdgeBatch
from repro.solve import Solver, sssp_problem

SEMIRINGS = {"min_plus": MIN_PLUS, "plus_times": PLUS_TIMES}
# Graph of _edge_case_graph: three workers, δ = 8, so S = 3.
BOUNDS = np.array([0, 20, 36, 48])
DELTA, HUB, N = 8, 3, 48


def _check_csr_order(sched, graph):
    """Slots in CSR order, padding last, ``row_last`` from ``indptr``."""
    dst = np.asarray(sched.dst_local)
    rows = np.asarray(sched.rows)
    last = np.asarray(sched.row_last)
    assert (np.diff(dst, axis=-1) >= 0).all()
    for s in range(sched.S):
        for p in range(sched.P):
            r = rows[s, p]
            real = r < graph.n
            deg = np.zeros(sched.delta, dtype=np.int64)
            deg[real] = graph.indptr[r[real] + 1] - graph.indptr[r[real]]
            m = int(deg.sum())
            np.testing.assert_array_equal(
                dst[s, p, :m], np.repeat(np.arange(sched.delta), deg)
            )
            assert (dst[s, p, m:] == sched.delta).all()
            np.testing.assert_array_equal(
                last[s, p], np.where(deg > 0, np.cumsum(deg) - 1, -1)
            )
    assert sched.longest_row == int(graph.in_degree.max())
    assert 2**sched.passes >= sched.longest_row


def _solver_graph(kind="sssp"):
    return make_graph("kron", scale=8, efactor=8, kind=kind, seed=3)


def _patch(g, sched):
    """Three inserts and three deletes that leave every stripe within ``M``.

    Each insert goes to a row of in-degree 1 whose cell is narrower than
    ``M``, from a source the row does not read yet; each delete takes the
    first in-edge of a row of in-degree 2 or more.
    """
    rows = np.asarray(sched.rows)
    width = (np.asarray(sched.dst_local) < sched.delta).sum(axis=-1)  # (S, P)
    inserts = []
    for u in np.nonzero(g.in_degree == 1)[0]:
        s, p, _ = np.argwhere(rows == u)[0]
        if width[s, p] < sched.M and len(inserts) < 3:
            v = next(v for v in range(g.n) if v != g.indices[g.indptr[u]])
            inserts.append((int(v), int(u), 5))
    deletes = [
        (int(g.indices[g.indptr[u]]), int(u))
        for u in np.nonzero(g.in_degree >= 2)[0][::50][:3]
    ]
    assert len(inserts) == len(deletes) == 3
    return EdgeBatch.from_ops(inserts=inserts, deletes=deletes)


@pytest.mark.parametrize("source", ["build", "apply_updates", "persist", "stripes"])
def test_csr_order_invariant(source, tmp_path):
    g = _solver_graph()
    kw = dict(n_workers=4, delta=16, min_chunk=16)
    if source == "build":
        sched = make_schedule(g, 4, 16, MIN_PLUS)
        _check_csr_order(sched, g)
        return
    if source == "apply_updates":
        sv = Solver(g, sssp_problem(), **kw)
        sv.apply_updates(_patch(g, sv.schedule()))
        sched = sv.schedule()
        assert sv.stats["schedule_builds"] == 1  # patched in place, not rebuilt
        _check_csr_order(sched, sv._sched_graph)
        return
    Solver(g, sssp_problem(), cache_dir=tmp_path, **kw).schedule()
    if source == "stripes":  # a new graph: its untouched stripes load by content
        g, _ = g.apply_updates(EdgeBatch.from_ops(inserts=[(9, 5, 3)]))
    sv = Solver(g, sssp_problem(), cache_dir=tmp_path, **kw)
    sched = sv.schedule()
    if source == "persist":
        assert sv.stats["schedule_builds"] == 0 and sv.stats["cache_loads"] >= 1
    else:
        assert sv.stats["stripe_loads"] >= 1 and sv.stats["stripe_builds"] >= 1
    _check_csr_order(sched, sv._sched_graph)


def test_longest_row_and_passes():
    assert [scan_passes(L) for L in (0, 1, 2, 3, 4, 5, 64, 65)] == [
        0, 0, 1, 2, 2, 3, 6, 7,
    ]
    row_last = np.array([[[-1, 2, -1, 3, 9], [0, -1, -1, -1, -1]]])
    assert longest_row(row_last) == 6  # rows of 3, 1, 6 and 1 slots


def _edge_case_graph(sr):
    """A hub row longer than half its cell, empty rows, an edgeless cell.

    Worker 0 holds rows [0, 20): cell 0 carries the hub (row 3, 30 in-edges)
    and cell 2 has four real rows and four padded ones past the block; worker
    1's second cell (rows [28, 36)) has no edges at all and its third cell no
    rows; rows 5, 10 and 44 have no in-edges.
    """
    rng = np.random.default_rng(11)
    src, dst = [], []
    for u in range(N):
        if u in (5, 10, 44) or 28 <= u < 36:
            continue
        deg = 30 if u == HUB else int(rng.integers(1, 4))
        src += list(rng.choice(N, size=deg, replace=False))
        dst += [u] * deg
    vals = (
        rng.integers(1, 20, len(src)).astype(np.int32)
        if sr is MIN_PLUS
        else rng.random(len(src)).astype(np.float32)
    )
    return CSRGraph.from_edges(N, np.array(src), np.array(dst), vals)


def _scatter_round(sched, sr, row_update, x_ext):
    """The round with XLA's segment scatter in place of the sorted scan."""
    seg_op = jax.ops.segment_min if sr is MIN_PLUS else jax.ops.segment_sum
    P, delta = sched.P, sched.delta
    feat = x_ext.shape[1:]
    for s in range(sched.S):
        val = sched.val[s].reshape(sched.val[s].shape + (1,) * len(feat))
        contrib = sr.mul(x_ext[sched.src[s]], val)
        seg = sched.dst_local[s] + (jnp.arange(P) * (delta + 1))[:, None]
        red = seg_op(
            contrib.reshape((-1,) + feat), seg.reshape(-1), num_segments=P * (delta + 1)
        ).reshape((P, delta + 1) + feat)[:, :delta]
        rows = sched.rows[s]
        new = row_update(x_ext[rows], red, rows)
        x_ext = x_ext.at[rows.reshape(-1)].set(
            new.reshape((-1,) + feat).astype(x_ext.dtype), mode="drop"
        )
    return x_ext


def _frontier(sr, shape, rng, axis=0):
    """A random extended frontier; ``axis`` is its vertex axis."""
    if sr is MIN_PLUS:
        x = rng.integers(0, 500, shape).astype(np.int32)
        x[rng.random(shape) < 0.3] = INT_INF
    else:
        x = rng.random(shape).astype(np.float32)
    np.moveaxis(x, axis, 0)[-1] = sr.zero  # the dump slot
    return jnp.asarray(x)


@pytest.mark.parametrize("frontier", ["vector", "matrix", "batch"])
@pytest.mark.parametrize("name", ["min_plus", "plus_times"])
def test_round_matches_segment_scatter(name, frontier):
    sr = SEMIRINGS[name]
    g = _edge_case_graph(sr)
    sched = make_schedule(g, 3, DELTA, sr, bounds=BOUNDS)
    row_last = np.asarray(sched.row_last)
    # the fixture has what the test claims to cover
    assert sched.S == 3 and sched.longest_row == 30 and sched.passes == 5
    assert 2 * g.in_degree[HUB] > g.indptr[DELTA] - g.indptr[0]  # hub > half
    assert (row_last[1, 1] == -1).all() and (np.asarray(sched.rows)[1, 1] < N).all()
    assert (np.asarray(sched.rows)[2, 0, 4:] == N).all()  # padded past the block
    if sr is MIN_PLUS:
        row_update = lambda old, red, rows: jnp.minimum(old, red)  # noqa: E731
    else:
        row_update = lambda old, red, rows: np.float32(0.15 / N) + red  # noqa: E731
    rnd = jax.jit(round_fn(sched, sr, row_update))

    def two_rounds(x):
        x = _scatter_round(sched, sr, row_update, x)
        return np.asarray(_scatter_round(sched, sr, row_update, x))

    rng = np.random.default_rng(5)
    if frontier == "batch":
        X = _frontier(sr, (4, N + 1), rng, axis=1)
        batched = jax.vmap(rnd)
        got = np.asarray(batched(batched(X)))
        want = np.stack([two_rounds(x) for x in X])
    else:
        shape = (N + 1,) if frontier == "vector" else (N + 1, 3)
        x = _frontier(sr, shape, rng)
        got = np.asarray(rnd(rnd(x)))
        want = two_rounds(x)
    if sr is MIN_PLUS:
        np.testing.assert_array_equal(got, want)
    else:  # a new summation order: rows of ≤ 30 terms, ~30 ulps of float32
        np.testing.assert_allclose(got, want, rtol=4e-6, atol=0)


def _two_worker_graph():
    """Rows [0, 8) and [16, 24) with 4 in-edges each, the rest with 1.

    Balanced blocks cut it at 16, so each worker's first cell holds 32
    slots (``M``) and its second 8; the longest row is 4, so 2 passes.
    """
    src, dst = [], []
    for u in range(32):
        deg = 4 if u % 16 < 8 else 1
        src += [(u + 1 + j) % 32 for j in range(deg)]
        dst += [u] * deg
    vals = (np.arange(len(src)) % 9 + 1).astype(np.int32)
    return CSRGraph.from_edges(32, np.array(src), np.array(dst), vals)


@pytest.mark.parametrize("grow", [2, 4])
def test_patch_past_scan_span_drops_schedule(grow):
    g = _two_worker_graph()
    kw = dict(n_workers=2, delta=8, min_chunk=8)
    sv = Solver(g, sssp_problem(), **kw)
    sched = sv.schedule()
    assert (sched.M, sched.longest_row, sched.passes) == (32, 4, 2)
    # row 8 (in-degree 1, a cell of 8 slots) gains ``grow`` in-edges
    sv.apply_updates(EdgeBatch.from_ops(inserts=[(20 + j, 8, 3) for j in range(grow)]))
    longest = max(4, 1 + grow)
    assert (8 in sv._schedules) == (longest <= 2**sched.passes)
    res = sv.solve()
    assert sv.stats["scan_passes"] == scan_passes(longest)
    assert sv.stats["longest_row"] == longest
    fresh = Solver(sv.graph, sssp_problem(), **kw).solve()
    np.testing.assert_array_equal(np.asarray(res.x), np.asarray(fresh.x))


@pytest.mark.parametrize("kind", ["sssp", "pagerank"])
def test_scan_work_recorded(kind):
    from repro.solve import pagerank_problem

    g = _solver_graph(kind)
    problem = sssp_problem() if kind == "sssp" else pagerank_problem()
    sv = Solver(g, problem, n_workers=4, delta=16, min_chunk=16)
    sched = sv.schedule()
    L = int(g.in_degree.max())
    assert sched.longest_row == L and sched.passes == scan_passes(L)
    assert sv.stats["longest_row"] == L
    assert sv.stats["scan_passes"] == scan_passes(L)
    (build,) = [r for r in spans.records("repro.schedule.build")][-1:]
    assert build.attrs == {"scan_passes": scan_passes(L), "longest_row": L}
