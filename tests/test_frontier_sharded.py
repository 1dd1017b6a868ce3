"""Frontier-sharded (owner-computes + halo-exchange) engine: exactness first.

Acceptance coverage for the frontier="halo" distribution discipline:

* frontier-sharded rounds are bit-identical to ``backend="jit"`` for all
  four problems (pagerank / sssp / cc / jacobi) — fixed point AND per round;
* a hypothesis property test drives random graphs × P × δ through the halo
  round against the single-device reference round;
* :class:`FrontierPlan` invariants: scatter/gather roundtrip, halo wire
  accounting below the replicated flush;
* batched sharded solving (replicated + halo) matches the jit batch, and
  ``compact_every`` (straggler compaction) preserves results while shrinking
  flush traffic.

Device-count adaptive: with 1 local device the mesh is 1-wide (halo sets are
empty but the full exchange machinery still runs); under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI matrix entry)
the same tests exercise real 8-way sharding.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms.jacobi import jacobi_graph
from repro.core.engine import make_schedule, round_fn
from repro.core.semiring import INT_INF, MIN_PLUS, PLUS_TIMES
from repro.dist.compat import make_mesh
from repro.dist.engine_sharded import (
    frontier_plan_args,
    frontier_round_ext_fn,
    make_frontier_plan,
)
from repro.graphs.formats import CSRGraph
from repro.graphs.generators import make_graph
from repro.solve import (
    Solver,
    cc_problem,
    jacobi_problem,
    multi_source_x0,
    pagerank_problem,
    ppr_problem,
    ppr_teleport,
    solve_batch,
    sssp_problem,
)

N_WORKERS = 8


def mesh_width() -> int:
    """Largest power-of-two device count dividing N_WORKERS."""
    return math.gcd(N_WORKERS, len(jax.devices()))


GRAPH_PR = make_graph("twitter", scale=9, efactor=8, kind="pagerank")
GRAPH_S = make_graph("kron", scale=8, efactor=8, kind="sssp")
GRAPH_U = make_graph("road", scale=8, kind="unit")


def _jacobi_case():
    rng = np.random.default_rng(0)
    n = 256
    rows = np.repeat(np.arange(n), 4)
    cols = (rows + rng.integers(1, n, rows.shape[0])) % n
    vals = rng.normal(size=rows.shape[0]).astype(np.float32) * 0.1
    diag = np.full(n, 4.0, np.float32)
    b = rng.normal(size=n).astype(np.float32)
    return jacobi_graph(n, rows, cols, vals, diag), jacobi_problem(diag, b)


CASES = {
    "pagerank": lambda: (GRAPH_PR, pagerank_problem()),
    "sssp": lambda: (GRAPH_S, sssp_problem()),
    "cc": lambda: (GRAPH_U, cc_problem()),
    "jacobi": _jacobi_case,
}


class TestFourProblemParity:
    @pytest.mark.parametrize("name", sorted(CASES))
    def test_fixed_point_bit_identical_to_jit(self, name):
        graph, problem = CASES[name]()
        solver = Solver(graph, problem, n_workers=N_WORKERS, delta=48, min_chunk=16)
        r_jit = solver.solve(backend="jit")
        r_halo = solver.solve(backend="sharded", frontier="halo")
        assert r_halo.rounds == r_jit.rounds
        np.testing.assert_array_equal(r_halo.x, r_jit.x)

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_per_round_bit_identical(self, name):
        graph, problem = CASES[name]()
        solver = Solver(graph, problem, n_workers=N_WORKERS, delta=48, min_chunk=16)
        rnd_host = solver.round_callable(backend="host")
        rnd_halo = solver.round_callable(backend="sharded", frontier="halo")
        x_h = x_s = solver._x_ext(None)
        for _ in range(3):
            x_h, x_s = rnd_host(x_h), rnd_halo(x_s)
            # owned frontier identical; the local dump slots differ by design
            np.testing.assert_array_equal(np.asarray(x_h[:-1]), np.asarray(x_s[:-1]))

    def test_ppr_query_threading_both_frontiers(self):
        solver = Solver(
            GRAPH_PR, ppr_problem(), n_workers=N_WORKERS, delta=64, min_chunk=16
        )
        q = ppr_teleport(GRAPH_PR, [5])[0]
        r_jit = solver.solve(q=q, backend="jit")
        r_rep = solver.solve(q=q, backend="sharded", frontier="replicated")
        r_halo = solver.solve(q=q, backend="sharded", frontier="halo")
        assert r_jit.rounds == r_rep.rounds == r_halo.rounds
        np.testing.assert_array_equal(r_jit.x, r_rep.x)
        np.testing.assert_array_equal(r_jit.x, r_halo.x)


class TestFrontierPlan:
    def _sched_plan(self, delta=32):
        sched = make_schedule(GRAPH_PR, N_WORKERS, delta, PLUS_TIMES)
        D = mesh_width()
        return sched, make_frontier_plan(sched, D), D

    def test_scatter_gather_roundtrip(self):
        sched, plan, _ = self._sched_plan()
        x_ext = jnp.concatenate(
            [jnp.arange(sched.n, dtype=jnp.float32), jnp.zeros((1,), jnp.float32)]
        )
        x_loc = plan.scatter_x(x_ext)
        assert x_loc.shape == (plan.D, plan.L)
        back = plan.gather_x(x_loc, dump=x_ext[-1:])
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x_ext))
        # halo slots hold their owners' values
        for d in range(plan.D):
            h = plan.halo_sizes[d]
            owned = plan.vertex_bounds[d + 1] - plan.vertex_bounds[d]
            if h:
                got = np.asarray(x_loc)[d, owned : owned + h]
                exp = np.asarray(x_ext)[
                    np.asarray(plan.gather_index)[d, owned : owned + h]
                ]
                np.testing.assert_array_equal(got, exp)

    def test_wire_accounting(self):
        sched, plan, D = self._sched_plan()
        assert plan.replicated_bytes_per_round(4) == sched.S * sched.P * sched.delta * 4
        assert plan.halo_bytes_per_round(4) == plan.S * plan.D * plan.H * 4
        if D > 1:
            # halo never ships more rows than the full flush publishes
            assert plan.boundary_entries_per_round <= sched.S * sched.P * sched.delta

    def test_plan_requires_divisible_workers(self):
        sched = make_schedule(GRAPH_PR, 6, 32, PLUS_TIMES)
        with pytest.raises(ValueError, match="not divisible"):
            make_frontier_plan(sched, 4)

    def test_worker_block_reads_the_matching_shard(self):
        """Plan builds copy a shard's own stripes, never slicing on device."""
        from types import SimpleNamespace

        sched = make_schedule(GRAPH_PR, N_WORKERS, 32, PLUS_TIMES)
        host = np.asarray(sched.src)
        np.testing.assert_array_equal(sched.worker_block("src", 2, 4), host[:, 2:4])
        shard = SimpleNamespace(
            index=(slice(None), slice(2, 4), slice(None)), data=host[:, 2:4]
        )
        fake = dataclasses.replace(
            sched, src=SimpleNamespace(addressable_shards=[shard])
        )
        assert fake.worker_block("src", 2, 4) is shard.data

    def test_sharded_solver_places_schedule_and_plan_per_shard(self):
        solver = Solver(
            GRAPH_PR, pagerank_problem(), n_workers=N_WORKERS, delta=64,
            backend="sharded", frontier="halo",
        )
        sched = solver.schedule()
        plan = solver.frontier_plan(sched)
        D = mesh_width()
        for arr in (sched.src, sched.val, plan.send_idx):
            assert len(arr.addressable_shards) == D
            assert arr.addressable_shards[0].data.shape[1] == arr.shape[1] // D
        assert plan.src_loc.addressable_shards[0].data.shape[0] == 1

    def test_plan_cached_on_solver(self):
        solver = Solver(
            GRAPH_PR, pagerank_problem(), n_workers=N_WORKERS, delta=64, min_chunk=16
        )
        solver.solve(backend="sharded", frontier="halo")
        snap = dict(solver.stats)
        assert snap["plan_builds"] == 1
        solver.solve(backend="sharded", frontier="halo")
        assert solver.stats["plan_builds"] == 1
        assert solver.stats["traces"] == snap["traces"]
        assert solver.stats["compiles"] == snap["compiles"]


class TestFrontierValidation:
    def test_explicit_halo_requires_sharded(self):
        solver = Solver(GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32)
        with pytest.raises(ValueError, match="requires backend='sharded'"):
            solver.solve(backend="jit", frontier="halo")

    def test_unknown_frontier_rejected(self):
        with pytest.raises(ValueError, match="frontier must be one of"):
            Solver(GRAPH_S, sssp_problem(), frontier="mirrored")

    def test_halo_default_falls_back_for_host_probes(self):
        """δ='auto' probes run backend='host'; a halo-default solver must not
        reject its own probes."""
        solver = Solver(
            GRAPH_PR,
            pagerank_problem(),
            n_workers=N_WORKERS,
            delta="auto",
            backend="sharded",
            frontier="halo",
            min_chunk=16,
        )
        r = solver.solve()
        assert r.converged


class TestShardedBatch:
    def test_batch_matches_jit_batch_both_frontiers(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        x0 = multi_source_x0(GRAPH_S, [0, 7, 33])
        b_jit = solve_batch(solver, x0)
        for frontier in ("replicated", "halo"):
            b = solve_batch(solver, x0, backend="sharded", frontier=frontier)
            assert b.rounds == b_jit.rounds, frontier
            np.testing.assert_array_equal(b.x, b_jit.x)
            np.testing.assert_array_equal(b.rounds_per_query, b_jit.rounds_per_query)

    def test_sharded_q1_matches_unbatched(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        r = solver.solve(backend="sharded", frontier="halo")
        b = solve_batch(
            solver, multi_source_x0(GRAPH_S, [0]), backend="sharded", frontier="halo"
        )
        assert b.rounds == r.rounds
        np.testing.assert_array_equal(b.x[0], r.x)

    def test_ppr_batch_sharded(self):
        solver = Solver(
            GRAPH_PR, ppr_problem(), n_workers=N_WORKERS, delta=64, min_chunk=16
        )
        seeds = [3, 11]
        q = ppr_teleport(GRAPH_PR, seeds)
        x0 = np.tile(np.full(GRAPH_PR.n, 1.0 / GRAPH_PR.n, np.float32), (2, 1))
        b_jit = solve_batch(solver, x0, q=q)
        b_halo = solve_batch(solver, x0, q=q, backend="sharded", frontier="halo")
        np.testing.assert_array_equal(b_jit.x, b_halo.x)


class TestStragglerCompaction:
    def _spread_sources(self, solver):
        probe = solve_batch(solver, multi_source_x0(GRAPH_S, list(range(16))))
        lo = int(probe.rounds_per_query.argmin())
        hi = int(probe.rounds_per_query.argmax())
        assert probe.rounds_per_query[lo] < probe.rounds_per_query[hi]
        return [lo, hi, 3]

    def test_compact_none_is_default_bit_for_bit(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        x0 = multi_source_x0(GRAPH_S, [0, 7])
        a = solve_batch(solver, x0)
        b = solve_batch(solver, x0, compact_every=None)
        assert a.compactions == b.compactions == 0
        np.testing.assert_array_equal(a.x, b.x)
        assert a.rounds == b.rounds and a.flush_bytes == b.flush_bytes

    def test_compact_exact_and_cheaper_minplus(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        x0 = multi_source_x0(GRAPH_S, self._spread_sources(solver))
        full = solve_batch(solver, x0)
        comp = solve_batch(solver, x0, compact_every=2)
        # min-plus is idempotent: compacted answers are exactly the full run's
        np.testing.assert_array_equal(comp.x, full.x)
        np.testing.assert_array_equal(comp.rounds_per_query, full.rounds_per_query)
        assert comp.converged.all()
        assert comp.compactions > 0
        assert comp.flush_bytes < full.flush_bytes
        assert comp.rounds == full.rounds

    def test_compact_with_sharded_backend(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        x0 = multi_source_x0(GRAPH_S, self._spread_sources(solver))
        full = solve_batch(solver, x0)
        comp = solve_batch(
            solver, x0, backend="sharded", frontier="halo", compact_every=2
        )
        np.testing.assert_array_equal(comp.x, full.x)
        np.testing.assert_array_equal(comp.rounds_per_query, full.rounds_per_query)

    def test_compact_rejects_nonpositive(self):
        solver = Solver(GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32)
        with pytest.raises(ValueError, match="compact_every"):
            solve_batch(solver, multi_source_x0(GRAPH_S, [0]), compact_every=0)

    def test_compact_respects_max_rounds(self):
        solver = Solver(
            GRAPH_S, sssp_problem(), n_workers=N_WORKERS, delta=32, min_chunk=8
        )
        x0 = multi_source_x0(GRAPH_S, [0, 7])
        b = solve_batch(solver, x0, compact_every=2, max_rounds=3)
        assert b.rounds <= 3


class TestShardedService:
    def test_serve_graph_sharded_halo_matches_jit(self):
        from repro.launch.serve_graph import GraphService

        from repro.launch.service import QueryRequest

        kwargs = dict(n_workers=N_WORKERS, delta=32, batch_size=2, min_chunk=8)
        base = GraphService(GRAPH_S, **kwargs)
        sharded = GraphService(
            GRAPH_S, backend="sharded", frontier="halo", compact_every=4, **kwargs
        )
        for svc in (base, sharded):
            for s in (0, 7):
                assert svc.submit(QueryRequest(algo="sssp", payload=s)).accepted
        d_base = {r.payload: r.x for r in base.drain()}
        d_shard = {r.payload: r.x for r in sharded.drain()}
        for s in (0, 7):
            np.testing.assert_array_equal(d_base[s], d_shard[s])


# --------------------------------------------------------------------------- #
# Property test: halo round ≡ reference round on random graphs × P × δ
# --------------------------------------------------------------------------- #
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    SETTINGS = dict(
        deadline=None,
        max_examples=15,
        suppress_health_check=[HealthCheck.too_slow],
    )

    @st.composite
    def random_case(draw):
        n = draw(st.integers(min_value=8, max_value=96))
        m = draw(st.integers(min_value=1, max_value=5 * n))
        seed = draw(st.integers(min_value=0, max_value=2**31 - 1))
        semiring = draw(st.sampled_from(["plus_times", "min_plus"]))
        p_loc = draw(st.integers(min_value=1, max_value=3))
        delta = draw(st.integers(min_value=1, max_value=24))
        rng = np.random.default_rng(seed)
        src = rng.integers(0, n, m)
        dst = rng.integers(0, n, m)
        if semiring == "min_plus":
            vals = rng.integers(1, 64, m).astype(np.int32)
        else:
            vals = (rng.random(m) * 0.2).astype(np.float32)
        g = CSRGraph.from_edges(n, src, dst, vals, name=f"h{seed}")
        return g, semiring, p_loc, delta, seed

    @given(random_case())
    @settings(**SETTINGS)
    def test_halo_round_bit_identical_property(case):
        g, sr_name, p_loc, delta, seed = case
        D = mesh_width()
        P = D * p_loc
        sr = MIN_PLUS if sr_name == "min_plus" else PLUS_TIMES
        sched = make_schedule(g, P, delta, sr)
        plan = make_frontier_plan(sched, D)
        mesh = make_mesh((D,), ("data",), devices=jax.devices()[:D])
        if sr_name == "min_plus":
            row_update_q = lambda o, r, w, q: jnp.minimum(o, r)
            rng = np.random.default_rng(seed)
            x0 = rng.integers(0, INT_INF, g.n, dtype=np.int32)
        else:
            row_update_q = lambda o, r, w, q: jnp.float32(0.01) + r
            rng = np.random.default_rng(seed)
            x0 = rng.random(g.n).astype(np.float32)
        row_update = lambda o, r, w: row_update_q(o, r, w, None)
        ref = jax.jit(round_fn(sched, sr, row_update))
        ext = jax.jit(frontier_round_ext_fn(sched, plan, sr, row_update_q, mesh))
        args = frontier_plan_args(sched, plan)
        x = jnp.concatenate(
            [jnp.asarray(x0, sr.dtype), jnp.asarray([sr.zero], sr.dtype)]
        )
        x_ref = x_halo = x
        for _ in range(3):
            x_ref = ref(x_ref)
            x_halo = ext(x_halo, jnp.zeros((), jnp.int32), *args)
            np.testing.assert_array_equal(
                np.asarray(x_ref[:-1]), np.asarray(x_halo[:-1])
            )
