"""repro.persist: warm-restart round trips, cache invalidation, δ re-probing.

Acceptance-criteria coverage for the persistent solver cache:

* a second Solver "process" (fresh instance, same ``cache_dir``) performs
  **zero stripe builds and zero retraces**, with results bit-identical to the
  cold run — for the fused jit loop, the host round, batched solving, and
  the sharded halo plan;
* every mismatch class — graph content, problem fingerprint (including
  row-update closure constants), repro/jax version bump, corrupted entry —
  is a clean **miss** (cold rebuild), never a wrong answer;
* ``delta="auto"`` resolves from the persisted δ-model without re-probing,
  and :meth:`Solver.reprobe_delta` refits from logged ``EngineResult``
  observations and migrates δ* without dropping compiled neighbors.
"""

import numpy as np
import pytest

from repro.core.delta_model import (
    DEVICE_COST_PARAMS,
    PLANNING_TARGET,
    DeltaModel,
    refit_delta_model,
)
from repro.graphs.generators import make_graph
from repro.solve import (
    Solver,
    multi_source_x0,
    pagerank_problem,
    solve_batch,
    sssp_problem,
)

GRAPH_PR = make_graph("twitter", scale=9, efactor=8, kind="pagerank")
GRAPH_S = make_graph("kron", scale=8, efactor=8, kind="sssp")


def pr_solver(cache_dir, graph=GRAPH_PR, problem=None, **kw):
    kw.setdefault("n_workers", 4)
    kw.setdefault("delta", 64)
    kw.setdefault("min_chunk", 16)
    return Solver(graph, problem or pagerank_problem(), cache_dir=cache_dir, **kw)


def assert_cold(solver):
    assert solver.stats["schedule_builds"] >= 1
    assert solver.stats["traces"] >= 1


def assert_warm(solver):
    assert solver.stats["schedule_builds"] == 0, solver.stats
    assert solver.stats["plan_builds"] == 0, solver.stats
    assert solver.stats["traces"] == 0, solver.stats
    assert solver.stats["compiles"] == 0, solver.stats
    assert solver.stats["cache_loads"] >= 1, solver.stats


class TestWarmRestart:
    def test_jit_round_trip_bit_identical_zero_work(self, tmp_path):
        cold = pr_solver(tmp_path)
        r_cold = cold.solve()
        assert_cold(cold)
        warm = pr_solver(tmp_path)
        r_warm = warm.solve()
        assert_warm(warm)
        assert r_warm.rounds == r_cold.rounds
        np.testing.assert_array_equal(r_cold.x, r_warm.x)

    def test_host_round_trip_int_semiring(self, tmp_path):
        cold = pr_solver(tmp_path, graph=GRAPH_S, problem=sssp_problem(), delta=32)
        r_cold = cold.solve(backend="host")
        warm = pr_solver(tmp_path, graph=GRAPH_S, problem=sssp_problem(), delta=32)
        r_warm = warm.solve(backend="host")
        assert_warm(warm)
        np.testing.assert_array_equal(r_cold.x, r_warm.x)

    def test_batch_round_trip(self, tmp_path):
        sources = [0, 7]
        x0 = multi_source_x0(GRAPH_S, sources)
        cold = pr_solver(tmp_path, graph=GRAPH_S, problem=sssp_problem(), delta=32)
        b_cold = solve_batch(cold, x0)
        warm = pr_solver(tmp_path, graph=GRAPH_S, problem=sssp_problem(), delta=32)
        b_warm = solve_batch(warm, x0)
        assert_warm(warm)
        assert b_warm.rounds == b_cold.rounds
        np.testing.assert_array_equal(b_cold.x, b_warm.x)

    def test_halo_plan_round_trip(self, tmp_path):
        kw = dict(backend="sharded", frontier="halo")
        cold = pr_solver(tmp_path, **kw)
        r_cold = cold.solve()
        assert cold.stats["plan_builds"] == 1
        warm = pr_solver(tmp_path, **kw)
        r_warm = warm.solve()
        # the plan and schedule must hydrate from disk; the shard_map
        # executable persists only when exported single-device, so assert
        # the build counters rather than traces here
        assert warm.stats["plan_builds"] == 0
        assert warm.stats["schedule_builds"] == 0
        assert warm.stats["cache_loads"] >= 2
        np.testing.assert_array_equal(r_cold.x, r_warm.x)

    def test_auto_delta_loads_without_probing(self, tmp_path):
        cold = pr_solver(tmp_path, delta="auto")
        cold.solve()
        assert cold.stats["solves"] >= 3  # two probes + the real solve
        delta_star = cold.resolve_delta("auto")
        warm = pr_solver(tmp_path, delta="auto")
        assert warm.resolve_delta("auto") == delta_star
        assert warm.stats["solves"] == 0  # δ-model loaded, no probe solves
        assert warm.delta_model is not None


class TestInvalidation:
    def test_graph_content_mismatch_is_cold(self, tmp_path):
        pr_solver(tmp_path).solve()
        perturbed = GRAPH_PR.with_values(
            (GRAPH_PR.values * np.float32(0.5)).astype(np.float32)
        )
        other = pr_solver(tmp_path, graph=perturbed)
        other.solve()
        assert_cold(other)

    def test_problem_fingerprint_mismatch_recompiles(self, tmp_path):
        pr_solver(tmp_path).solve()
        # same problem name, different row-update closure constant (teleport)
        other = pr_solver(tmp_path, problem=pagerank_problem(damping=0.9))
        other.solve()
        # the compiled loop bakes the constant in: always a cold retrace
        assert other.stats["traces"] >= 1
        assert other.stats["compiles"] >= 1
        # the schedule holds only graph bytes — the content-addressed stripe
        # store may (and does) share it across problem namespaces
        assert other.stats["schedule_builds"] == 0
        assert other.stats["stripe_loads"] == other.n_workers

    def test_version_bump_is_cold(self, tmp_path, monkeypatch):
        cold = pr_solver(tmp_path)
        r_cold = cold.solve()
        monkeypatch.setattr("repro.persist.keys._REPRO_VERSION", "bumped")
        other = pr_solver(tmp_path)
        r_other = other.solve()
        assert_cold(other)
        np.testing.assert_array_equal(r_cold.x, r_other.x)

    def test_device_change_is_cold(self, tmp_path, monkeypatch):
        """A cache filled on one platform never warms a process on another."""
        from types import SimpleNamespace as Dev

        from repro.persist import keys

        chip = Dev(platform="tpu", device_kind="TPU v5 lite")
        cpu = Dev(platform="cpu", device_kind="cpu")
        assert keys.env_fingerprint(chip) != keys.env_fingerprint(cpu)
        cold = pr_solver(tmp_path)
        cold.solve()
        real = keys.env_fingerprint
        monkeypatch.setattr(keys, "env_fingerprint", lambda device=None: real(chip))
        other = pr_solver(tmp_path)
        assert other.persist.dir != cold.persist.dir
        other.solve()
        assert_cold(other)

    def test_corrupt_entries_fall_back_cold(self, tmp_path):
        cold = pr_solver(tmp_path)
        r_cold = cold.solve()
        corrupted = 0
        for path in tmp_path.rglob("*"):
            if path.suffix in (".npz", ".bin", ".json"):
                path.write_bytes(b"\x00corrupt\xff")
                corrupted += 1
        assert corrupted >= 2  # schedule + executable at minimum
        warm = pr_solver(tmp_path)
        r_warm = warm.solve()
        assert_cold(warm)  # every load was a miss, never an exception
        np.testing.assert_array_equal(r_cold.x, r_warm.x)

    def test_truncated_observation_line_skipped(self, tmp_path):
        solver = pr_solver(tmp_path)
        solver.solve()
        store = solver.persist
        n_before = len(store.load_observations())
        assert n_before >= 1
        with open(store.dir / "observations.jsonl", "a") as f:
            f.write('{"delta": 64, "rou')  # killed mid-write
        assert len(store.load_observations()) == n_before
        store.record_observation(64, 5, 0.1, backend="jit")
        # the partial line has no newline; the reader must still see the
        # well-formed rows on either side of it
        assert len(store.load_observations()) >= n_before


class TestDeltaReprobing:
    @staticmethod
    def _model(r_sync, r_async):
        return DeltaModel(
            P=4,
            B=4096,
            delta_min=16,
            r_sync=r_sync,
            r_async=r_async,
            locality=0.0,
            edges=200_000,
            bytes_per_elem=4,
            hw=DEVICE_COST_PARAMS[PLANNING_TARGET],
        )

    def test_refit_flat_observations_push_delta_up(self):
        """Flat rounds(δ) ⇒ no freshness benefit ⇒ commit cost picks big δ."""
        model = self._model(r_sync=1000, r_async=10)
        assert model.best_delta() < model.B
        flat = [(16, 60), (256, 60), (4096, 60)] * 5
        refit = refit_delta_model(model, flat)
        assert abs(refit.r_sync - refit.r_async) < abs(model.r_sync - model.r_async)
        assert refit.best_delta() > model.best_delta()

    def test_refit_steep_observations_push_delta_down(self):
        """Steep rounds(δ) ⇒ strong freshness benefit ⇒ finer δ wins."""
        model = self._model(r_sync=50, r_async=48)
        steep = [(16, 10), (256, 200), (4096, 2000)] * 5
        refit = refit_delta_model(model, steep)
        assert refit.r_sync > refit.r_async
        assert refit.best_delta() <= model.best_delta()

    def test_refit_empty_observations_keeps_model(self):
        model = self._model(r_sync=100, r_async=10)
        refit = refit_delta_model(model, [])
        assert refit.best_delta() == model.best_delta()
        assert np.isclose(refit.r_sync, model.r_sync)
        assert np.isclose(refit.r_async, model.r_async)

    def test_reprobe_migrates_without_dropping_neighbors(self, tmp_path):
        # Seed the store with a fitted δ-model whose freshness gap strongly
        # favors a *fine* δ (as a first probe on an async-friendly graph
        # would), so flat production observations have room to migrate up.
        seed = pr_solver(tmp_path, delta=64)
        seed.solve()
        base = DeltaModel(
            P=4,
            B=seed.block_size,
            delta_min=16,
            r_sync=1000,
            r_async=10,
            locality=0.0,
            edges=seed.graph.nnz,
            bytes_per_elem=4,
            hw=DEVICE_COST_PARAMS[PLANNING_TARGET],
        )
        assert base.best_delta() < base.B
        seed.persist.save_delta_model(base, base.best_delta())

        solver = pr_solver(tmp_path, delta="auto")
        old_star = solver.resolve_delta("auto")
        assert old_star == base.best_delta()  # served from the store, no probe
        assert solver.stats["solves"] == 0
        solver.solve()  # compiles the old δ*'s executable
        compiled_before = set(solver._compiled)
        schedules_before = set(solver._schedules)
        # Production logs a flat rounds(δ) curve: delaying costs no extra
        # rounds on this workload, so the commit-cost term should win and
        # δ* should migrate up.
        for d in (16, old_star, solver.block_size):
            for _ in range(10):
                solver.persist.record_observation(d, 40, 0.01, backend="jit")
        migrated_from, new_star = solver.reprobe_delta()
        assert migrated_from == old_star
        assert new_star == solver.resolve_delta("auto")
        assert new_star > old_star
        # nothing dropped: every already-compiled executable and schedule
        # for the old δ* (and any neighbor) is still warm in memory
        assert compiled_before <= set(solver._compiled)
        assert schedules_before <= set(solver._schedules)
        # the migration is persisted: a restarted process serves the new δ*
        warm = pr_solver(tmp_path, delta="auto")
        assert warm.resolve_delta("auto") == new_star
        assert warm.stats["solves"] == 0

    def test_batch_observations_drive_reprobe(self, tmp_path):
        """Served batches are production traffic: they must advance the refit
        counter and feed the fit (a serving process emits nothing else)."""
        x0 = multi_source_x0(GRAPH_S, [0, 7])
        solver = pr_solver(
            tmp_path,
            graph=GRAPH_S,
            problem=sssp_problem(),
            delta="auto",
            reprobe_every=1,
        )
        solve_batch(solver, x0)
        obs = solver.persist.load_observations()
        assert any(o["kind"] == "batch" for o in obs)
        # the batch observation crossed reprobe_every, so a refit ran inline
        assert solver._obs_since_refit == 0
        assert solver.persist.load_delta_model() is not None

    def test_reprobe_every_refits_inline(self, tmp_path):
        solver = pr_solver(tmp_path, delta="auto", reprobe_every=1)
        solver.solve()
        # the auto-probe + solve recorded ≥ reprobe_every observations, so a
        # refit ran inline and reset the counter
        assert solver._obs_since_refit == 0
        assert solver.persist.load_delta_model() is not None

    def test_reprobe_requires_cache_dir(self):
        solver = Solver(GRAPH_PR, pagerank_problem(), n_workers=4, delta=64)
        with pytest.raises(ValueError, match="cache_dir"):
            solver.reprobe_delta()


class TestNamespaceKeys:
    def test_closure_constants_distinguish_problems(self, tmp_path):
        """Two Jacobi systems on one graph differ only in baked-in b."""
        from repro.algorithms.jacobi import jacobi_graph
        from repro.solve import jacobi_problem

        rng = np.random.default_rng(0)
        n = 128
        rows = np.repeat(np.arange(n), 4)
        cols = (rows + rng.integers(1, n, rows.shape[0])) % n
        vals = rng.normal(size=rows.shape[0]).astype(np.float32) * 0.1
        diag = np.full(n, 4.0, np.float32)
        g = jacobi_graph(n, rows, cols, vals, diag)
        b1 = rng.normal(size=n).astype(np.float32)
        b2 = rng.normal(size=n).astype(np.float32)
        s1 = pr_solver(tmp_path, graph=g, problem=jacobi_problem(diag, b1))
        s2 = pr_solver(tmp_path, graph=g, problem=jacobi_problem(diag, b2))
        assert s1.persist.namespace != s2.persist.namespace
        # sanity: the same problem maps to the same namespace
        s1b = pr_solver(tmp_path, graph=g, problem=jacobi_problem(diag, b1))
        assert s1.persist.namespace == s1b.persist.namespace

    def test_no_cache_dir_no_persistence(self, tmp_path):
        solver = Solver(GRAPH_PR, pagerank_problem(), n_workers=4, delta=64)
        solver.solve()
        assert solver.persist is None
        assert solver.stats["cache_loads"] == 0
        assert not any(tmp_path.iterdir())


class TestServeGraphGate:
    def test_serve_graph_warm_restart_gate(self, tmp_path):
        """The exact round trip the CI warm-start job runs, in-process."""
        from repro.launch.serve_graph import main

        argv = (
            "--graph kron --scale 8 --queries 2 --repeats 2 --delta 32 "
            f"--algo sssp --cache-dir {tmp_path}"
        ).split()
        cold = main(argv)
        assert cold["stats"]["sssp"]["schedule_builds"] == 1
        warm = main(argv + ["--assert-warm"])  # raises SystemExit if cold
        assert warm["stats"]["sssp"]["schedule_builds"] == 0
        assert warm["stats"]["sssp"]["traces"] == 0
        np.testing.assert_array_equal(
            np.asarray(cold["latency_s"]["sssp"]).shape,
            np.asarray(warm["latency_s"]["sssp"]).shape,
        )

    def test_assert_warm_fails_on_empty_cache(self, tmp_path):
        from repro.launch.serve_graph import main

        argv = (
            "--graph kron --scale 8 --queries 2 --repeats 1 --delta 32 "
            f"--algo sssp --cache-dir {tmp_path / 'empty'} --assert-warm"
        ).split()
        with pytest.raises(SystemExit, match="cold work performed"):
            main(argv)
