"""Chaos harness, elastic solve checkpoints, persist hardening, FT runner.

Acceptance-criteria coverage for the fault-tolerance tier:

* ``FaultPlan`` firing is a pure function of the call sequence
  (``at`` / ``every`` / seeded ``p`` / ``match`` / ``times``) and plans
  round-trip through JSON, so committed chaos traces replay identically;
* ``checkpointed_solve`` resumes **bit-identically** — same per-round
  trajectory and fixed point as the uninterrupted solve — after injected
  faults, after a simulated process kill, and from a cold start;
* torn / corrupt / EIO checkpoint and cache writes read as *absent*
  (cold start / cache miss), never as exceptions, and concurrent cache
  writers never publish torn bytes (unique tmp + atomic replace);
* delayed-commit state reshards elastically: same pod count resumes
  bit-identical, a different count folds buffered deltas into the global
  store (fixed-point-identical);
* the training runner counts every step's loss exactly once across
  restore-and-replay (the history truncation fix).
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager, latest_step, save_checkpoint
from repro.dist.delayed_commit import DelayedCommitState, reshard_delayed_state
from repro.ft.elastic import checkpointed_solve, restore_delayed_state
from repro.ft.inject import FaultPlan, FaultSpec, InjectedFault, active_plan, inject
from repro.ft.runner import FailureInjector, RunnerConfig, run_training
from repro.graphs.generators import make_graph
from repro.persist.store import SolverCache
from repro.solve import Solver, sssp_problem

GRAPH_S = make_graph("kron", scale=8, efactor=8, kind="sssp")


def sssp_solver(**kw):
    kw.setdefault("n_workers", 4)
    kw.setdefault("delta", 32)
    kw.setdefault("min_chunk", 8)
    return Solver(GRAPH_S, sssp_problem(), **kw)


class TestFaultPlan:
    def test_at_and_times(self):
        plan = FaultPlan([FaultSpec(site="s", at=2, times=2)])
        fired = []
        for visit in range(6):
            try:
                plan.fire("s")
            except InjectedFault:
                fired.append(visit)
        assert fired == [2, 3]
        assert plan.fired == 2

    def test_every_unlimited(self):
        plan = FaultPlan([FaultSpec(site="s", every=3, times=-1)])
        fired = []
        for visit in range(9):
            try:
                plan.fire("s")
            except InjectedFault:
                fired.append(visit)
        assert fired == [2, 5, 8]

    def test_match_filters_context(self):
        plan = FaultPlan([FaultSpec(site="k", match={"backend": "pallas"})])
        assert plan.fire("k", backend="jit") is None
        assert plan.fire("k") is None  # absent context key never matches
        with pytest.raises(InjectedFault):
            plan.fire("k", backend="pallas")

    def test_io_kinds_returned_not_raised(self):
        plan = FaultPlan([FaultSpec(site="w", kind="torn", times=-1, at=0)])
        assert plan.fire("w") == "torn"
        assert plan.fire("r") is None  # other sites untouched

    def test_seeded_p_deterministic(self):
        def run(seed):
            plan = FaultPlan([FaultSpec(site="s", p=0.3, times=-1)], seed=seed)
            out = []
            for _ in range(40):
                try:
                    plan.fire("s")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert run(7) == run(7)
        assert sum(run(7)) > 0

    def test_json_roundtrip_replays_identically(self):
        plan = FaultPlan(
            [
                FaultSpec(site="a", at=1, times=2, match={"round": 3}),
                FaultSpec(site="b", kind="eio", every=2, times=-1),
            ],
            seed=5,
        )
        back = FaultPlan.loads(plan.dumps())
        seq = [("a", {"round": 3}), ("b", {}), ("a", {"round": 0}), ("b", {})]

        def trace(p):
            out = []
            for _ in range(3):
                for site, ctx in seq:
                    try:
                        out.append(p.fire(site, **ctx))
                    except InjectedFault:
                        out.append("raised")
            return out

        assert trace(plan) == trace(back)
        assert plan.events == back.events

    def test_inject_context_scopes_plan(self):
        from repro.ft.inject import fire

        assert active_plan() is None
        assert fire("anything") is None  # no plan installed: no-op
        plan = FaultPlan([FaultSpec(site="s")])
        with inject(plan):
            assert active_plan() is plan
            with pytest.raises(InjectedFault):
                fire("s")
        assert active_plan() is None
        assert plan.sites_fired() == ["s"]


class TestCheckpointedSolve:
    def test_no_fault_matches_plain_solve(self, tmp_path):
        # host reference: same bit-identical rounds as jit, but the host
        # loop records per-round residuals (the fused jit path keeps only
        # the final one), so the whole trajectory is comparable
        solver = sssp_solver()
        ref = solver.solve(backend="host")
        out = checkpointed_solve(
            sssp_solver(), backend="jit", ckpt_dir=tmp_path, every=4
        )
        assert out.restores == 0 and out.resumed_at is None
        assert out.result.rounds == ref.rounds
        np.testing.assert_array_equal(out.result.x, ref.x)
        np.testing.assert_array_equal(out.result.residuals, ref.residuals)

    def test_fault_restores_and_stays_bit_identical(self, tmp_path):
        ref = sssp_solver().solve(backend="host")
        plan = FaultPlan([FaultSpec(site="solver.round", match={"round": 6})])
        with inject(plan):
            out = checkpointed_solve(
                sssp_solver(), backend="jit", ckpt_dir=tmp_path, every=4
            )
        assert plan.fired == 1
        assert out.restores == 1
        # killed at round 6, restored to the round-4 snapshot: 2 replayed
        assert out.rounds_executed == ref.rounds + 2
        assert out.result.rounds == ref.rounds
        np.testing.assert_array_equal(out.result.x, ref.x)
        np.testing.assert_array_equal(out.result.residuals, ref.residuals)

    def test_cold_restart_before_first_snapshot(self, tmp_path):
        ref = sssp_solver().solve(backend="host")
        plan = FaultPlan([FaultSpec(site="solver.round", match={"round": 2})])
        with inject(plan):
            out = checkpointed_solve(
                sssp_solver(), backend="jit", ckpt_dir=tmp_path, every=64
            )
        assert out.restores == 1
        assert out.rounds_executed == ref.rounds + 2  # full replay from 0
        np.testing.assert_array_equal(out.result.x, ref.x)

    def test_kill_and_resume_fresh_process(self, tmp_path):
        """Simulated kill -9 mid-solve; a fresh solver resumes from disk."""
        ref = sssp_solver().solve(backend="host")
        plan = FaultPlan([FaultSpec(site="solver.round", match={"round": 6})])
        with inject(plan):
            with pytest.raises(InjectedFault):
                checkpointed_solve(
                    sssp_solver(),
                    backend="jit",
                    ckpt_dir=tmp_path,
                    every=4,
                    max_restores=0,  # the "process" dies on the first fault
                )
        out = checkpointed_solve(
            sssp_solver(), backend="jit", ckpt_dir=tmp_path, every=4
        )
        assert out.resumed_at == 4
        assert out.rounds_executed == ref.rounds - 4
        assert out.result.rounds == ref.rounds
        np.testing.assert_array_equal(out.result.x, ref.x)
        np.testing.assert_array_equal(out.result.residuals, ref.residuals)

    def test_max_restores_exhausted_raises(self, tmp_path):
        plan = FaultPlan([FaultSpec(site="solver.round", at=0, times=-1)])
        with inject(plan):
            with pytest.raises(InjectedFault):
                checkpointed_solve(
                    sssp_solver(),
                    backend="jit",
                    ckpt_dir=tmp_path,
                    every=4,
                    max_restores=2,
                )
        assert plan.fired == 3  # initial fault + max_restores failed retries


def _toy_delayed_state(n_pods=2, delta=1.0):
    gp = {"w": jnp.arange(6.0, dtype=jnp.float32).reshape(2, 3)}
    return DelayedCommitState(
        global_params=gp,
        local_delta={"w": jnp.full((n_pods, 2, 3), delta, jnp.float32)},
        opt_state={
            "m": jnp.ones((n_pods, 2, 3), jnp.float32),
            "count": jnp.asarray(9, jnp.int32),
        },
        step=jnp.asarray(5, jnp.int32),
    )


class TestElasticDelayedState:
    def test_same_width_is_identity(self):
        state = _toy_delayed_state(n_pods=2)
        back = reshard_delayed_state(state, 2)
        assert back is state  # bit-identical resume, no copies

    def test_different_width_folds_deltas(self):
        state = _toy_delayed_state(n_pods=2, delta=1.0)
        back = reshard_delayed_state(state, 4)
        # one flush-equivalent commit: mean of per-pod deltas folds in
        np.testing.assert_array_equal(
            np.asarray(back.global_params["w"]),
            np.asarray(state.global_params["w"]) + 1.0,
        )
        assert back.local_delta["w"].shape == (4, 2, 3)
        assert not np.asarray(back.local_delta["w"]).any()
        assert back.opt_state["m"].shape == (4, 2, 3)
        assert int(back.opt_state["count"]) == 9  # shared scalar passes through
        assert int(back.step) == 5

    def test_restore_roundtrip_and_elastic(self, tmp_path):
        state = _toy_delayed_state(n_pods=2, delta=0.5)
        save_checkpoint(tmp_path, 3, state)
        step, same = restore_delayed_state(tmp_path, state, n_pods=2)
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(same.local_delta["w"]), np.asarray(state.local_delta["w"])
        )
        step, wider = restore_delayed_state(tmp_path, state, n_pods=4)
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(wider.global_params["w"]),
            np.asarray(state.global_params["w"]) + 0.5,
        )
        assert wider.local_delta["w"].shape == (4, 2, 3)

    def test_restore_missing_or_mismatched_is_none(self, tmp_path):
        state = _toy_delayed_state()
        assert restore_delayed_state(tmp_path, state, 2) == (None, None)
        save_checkpoint(tmp_path, 1, {"other": jnp.zeros(3)})
        assert restore_delayed_state(tmp_path, state, 2) == (None, None)


class TestCheckpointFaults:
    def test_torn_commit_is_invisible(self, tmp_path):
        tree = {"x": jnp.arange(4.0)}
        with inject(FaultPlan([FaultSpec(site="ckpt.write", kind="torn")])):
            save_checkpoint(tmp_path, 5, tree)
        # shards + manifest landed but _COMMITTED never did: restart skips it
        assert (tmp_path / "step_000000005" / "manifest.json").exists()
        assert latest_step(tmp_path) is None
        save_checkpoint(tmp_path, 7, tree)
        assert latest_step(tmp_path) == 7

    def test_eio_write_raises_and_runner_survives(self, tmp_path):
        tree = {"x": jnp.arange(4.0)}
        with inject(FaultPlan([FaultSpec(site="ckpt.write", kind="eio")])):
            with pytest.raises(OSError):
                save_checkpoint(tmp_path, 5, tree)
        assert latest_step(tmp_path) is None

    def test_manager_gc_keeps_last_k(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        for step in (1, 2, 3, 4):
            mgr.save(step, {"x": jnp.asarray(float(step))}, block=True)
        assert latest_step(tmp_path) == 4
        committed = sorted(
            p.name for p in tmp_path.iterdir() if p.name.startswith("step_")
        )
        assert committed == ["step_000000003", "step_000000004"]


def _stripe(fill: int) -> dict:
    return {
        "src": np.full(8, fill, np.int64),
        "val": np.full(8, float(fill), np.float32),
        "dst_local": np.arange(8, dtype=np.int64),
        "rows": np.arange(8, dtype=np.int64),
        "row_last": np.arange(8, dtype=np.int64),
    }


class TestPersistFaults:
    @pytest.mark.parametrize("kind", ["torn", "corrupt", "eio"])
    def test_injected_write_fault_reads_as_miss(self, tmp_path, kind):
        cache = SolverCache(tmp_path, "f" * 16)
        digest = "a" * 24
        with inject(FaultPlan([FaultSpec(site="persist.write", kind=kind)])):
            cache.save_stripe(digest, _stripe(3))  # must not raise
        assert cache.load_stripe(digest) is None  # corruption ⇒ miss
        cache.save_stripe(digest, _stripe(3))  # clean retry heals
        got = cache.load_stripe(digest)
        np.testing.assert_array_equal(got["src"], _stripe(3)["src"])

    def test_injected_read_fault_is_transient_miss(self, tmp_path):
        cache = SolverCache(tmp_path, "f" * 16)
        digest = "b" * 24
        cache.save_stripe(digest, _stripe(7))
        with inject(FaultPlan([FaultSpec(site="persist.read", kind="eio")])):
            assert cache.load_stripe(digest) is None
        got = cache.load_stripe(digest)  # the bytes were never damaged
        np.testing.assert_array_equal(got["val"], _stripe(7)["val"])

    def test_concurrent_writers_never_publish_torn_bytes(self, tmp_path):
        cache = SolverCache(tmp_path, "f" * 16)
        digest = "c" * 24
        errors = []

        def hammer(fill):
            try:
                for _ in range(30):
                    cache.save_stripe(digest, _stripe(fill))
                    got = cache.load_stripe(digest)
                    if got is None:
                        continue  # a miss is legal mid-race; torn data is not
                    v = int(got["src"][0])
                    assert v in (1, 2)
                    assert (got["src"] == v).all()
                    assert (got["val"] == float(v)).all()
            except Exception as err:  # pragma: no cover - failure path
                errors.append(err)

        threads = [threading.Thread(target=hammer, args=(f,)) for f in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        final = cache.load_stripe(digest)  # last writer wins, file is whole
        assert final is not None and int(final["src"][0]) in (1, 2)


def _toy_training(tmp_path, injector=None, total_steps=12, ckpt_every=4):
    """Tiny deterministic training loop: loss of step i is i(i+1)/2."""
    state = {"x": jnp.zeros(()), "step": jnp.zeros((), jnp.int32)}

    def step_fn(s, b):
        x = s["x"] + b
        return {"x": x, "step": s["step"] + 1}, {"loss": x}

    cfg = RunnerConfig(
        total_steps=total_steps, ckpt_every=ckpt_every, ckpt_dir=str(tmp_path)
    )
    return run_training(
        state, step_fn, lambda s: jnp.asarray(float(s)), cfg, injector=injector
    )


class TestRunnerReplayAccounting:
    def test_replay_counts_each_step_once(self, tmp_path):
        _, clean = _toy_training(tmp_path / "clean")
        state, hist = _toy_training(
            tmp_path / "faulted", injector=FailureInjector(fail_at=[6, 10])
        )
        assert hist["restarts"] == 2
        assert int(state["step"]) == 12
        # the fix under test: replayed steps overwrite, they don't append
        assert len(hist["loss"]) == 12
        assert hist["loss"] == clean["loss"]

    def test_cold_restart_replay_accounting(self, tmp_path):
        _, clean = _toy_training(tmp_path / "clean", total_steps=6, ckpt_every=100)
        _, hist = _toy_training(
            tmp_path / "faulted",
            injector=FailureInjector(fail_at=[3]),
            total_steps=6,
            ckpt_every=100,  # nothing committed before the fault: cold restart
        )
        assert hist["restarts"] == 1
        assert len(hist["loss"]) == 6
        assert hist["loss"] == clean["loss"]

    def test_faultplan_injector_and_global_plan(self, tmp_path):
        plan = FaultPlan([FaultSpec(site="train.step", match={"step": 5})])
        _, hist = _toy_training(tmp_path / "direct", injector=plan, total_steps=8)
        assert hist["restarts"] == 1 and plan.fired == 1
        globally = FaultPlan([FaultSpec(site="train.step", match={"step": 5})])
        with inject(globally):
            _, hist2 = _toy_training(tmp_path / "ambient", total_steps=8)
        assert hist2["restarts"] == 1 and globally.fired == 1
        assert hist["loss"] == hist2["loss"]
