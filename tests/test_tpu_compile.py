"""Compile the engine's device programs for a described TPU v5e (no chip needed).

The TPU compiler is installed alongside jax, so it can compile for a v5e
that is described rather than attached.  These tests compile the programs a
chip run executes — the jit solve loop, the batched serving loop, the
four-chip halo round — and refuse anything the chip's compiler would refuse.
Nothing runs, so they say nothing about results or times.

The Pallas kernels do not lower for TPU yet (their 1-D ``x[src]`` frontier
gather is refused by Mosaic); those two cases are strict xfails, so the day
they lower cannot go unnoticed.

The topology is described inside a module fixture, never at import time: only
one process at a time may load the TPU library, and the test workers each
import every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import (
    DeviceSchedule,
    make_schedule,
    make_solve_fn_q_dyn,
    schedule_args,
)
from repro.dist.compat import make_mesh
from repro.dist.engine_sharded import (
    frontier_plan_args,
    frontier_round_ext_fn,
    make_frontier_plan,
)
from repro.graphs.generators import make_graph
from repro.kernels.round_block import fused_halo_step_fn, fused_round_fn_q
from repro.solve import (
    Solver,
    pagerank_problem,
    ppr_problem,
    rwr_embedding_problem,
    sssp_problem,
)
from repro.solve.batch import _batched_round, _make_open_batch_solve_fn

SCALE, WORKERS, DELTA = 12, 8, 128
PROBLEMS = {"pagerank": pagerank_problem, "sssp": sssp_problem, "ppr": ppr_problem}
# The benchmark cell kron.hubppr's schedule on a v5e (GAP kron scale 18, δ=16384,
# 8 workers; seed 2147483011): its static shapes, for a compile without the graph.
HUB = {"n": 2**18, "S": 3, "M": 499_707, "passes": 15, "F": 16}
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def graphs():
    g = make_graph("kron", scale=SCALE, efactor=16, kind="pagerank")
    return {"pagerank": g, "ppr": g, "sssp": make_graph("kron", SCALE, 16, kind="sssp")}


def _solver(graphs, name):
    return Solver(graphs[name], PROBLEMS[name](), n_workers=WORKERS, delta=DELTA)


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a), sharding=sharding)


@pytest.mark.parametrize("name", ["pagerank", "sssp"])
def test_jit_solve_loop_compiles_for_v5e(graphs, one_chip, name):
    """The Solver's default path: the fused dynamic-schedule while loop."""
    solver = _solver(graphs, name)
    sched = solver.schedule()
    x_ext = solver._x_ext(None)
    q = solver.resolve_query(None)
    fn = make_solve_fn_q_dyn(
        sched, solver.problem.semiring, solver._row_update_q, solver.problem.residual
    )
    args = (
        x_ext,
        q,
        *schedule_args(sched),
        jnp.float32(solver.tol),
        jnp.int32(solver.max_rounds),
    )
    compiled = jax.jit(fn).lower(*(_spec(a, one_chip) for a in args)).compile()
    stripes = sum(a.nbytes for a in schedule_args(sched))
    assert compiled.memory_analysis().argument_size_in_bytes >= stripes
    assert "tpu_custom_call" not in compiled.as_text()  # plain XLA, no kernel


def test_hub_matrix_solve_loop_compiles_for_v5e(one_chip):
    """The (n, 16) hub-PPR solve loop at the cell's shapes fits one chip.

    The compiler keeps the ``(P, M, 16)`` slot arrays with the slot axis
    minor and hands the frontier in and out with the vertex axis minor, so
    neither is padded from 16 lanes to 128 (inside the loop it keeps an
    8x padded row-major copy of the frontier, 134 MB at this size).
    """
    n, S, M, F = HUB["n"], HUB["S"], HUB["M"], HUB["F"]
    sched = DeviceSchedule(
        n=n, P=WORKERS, delta=16384, S=S, M=M, src=None, val=None,
        dst_local=None, rows=None, row_last=None, edges=0, padding_overhead=1.0,
        passes=HUB["passes"], longest_row=2 ** HUB["passes"],
    )
    problem = rwr_embedding_problem(feature_dim=F)
    fn = make_solve_fn_q_dyn(
        sched, problem.semiring, problem.make_row_update(None), problem.residual
    )
    i32, f32 = jnp.int32, jnp.float32
    shapes = [
        ((n + 1, F), f32), ((n, F), f32),
        ((S, WORKERS, M), i32), ((S, WORKERS, M), f32), ((S, WORKERS, M), i32),
        ((S, WORKERS, 16384), i32), ((S, WORKERS, 16384), i32), ((), f32), ((), i32),
    ]
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    mem = jax.jit(fn).lower(*specs).compile().memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < V5E_HBM_BYTES
    assert mem.output_size_in_bytes < 1.01 * (n + 1) * F * 4  # not lane-padded


@pytest.mark.parametrize("name", ["sssp", "ppr"])
def test_serving_loop_compiles_for_v5e_at_q8(graphs, one_chip, name):
    """The GraphService lane: the open batch loop over 8 slots, jit backend."""
    solver = _solver(graphs, name)
    sched = solver.schedule()
    rnd, sargs = _batched_round(solver, sched, "jit", "replicated")
    fn = _make_open_batch_solve_fn(rnd, solver.problem.residual)
    Q, n = 8, solver.graph.n
    sr = solver.problem.semiring
    qb = (
        np.zeros((Q, n), np.float32)
        if solver.problem.takes_query
        else np.zeros((Q,), np.int32)
    )
    args = (
        np.zeros((Q, n + 1), sr.dtype),
        qb,
        np.zeros((Q,), bool),
        np.float32(solver.tol),
        np.int32(8),
        *sargs,
    )
    compiled = jax.jit(fn).lower(*(_spec(a, one_chip) for a in args)).compile()
    # schedule-as-data: the stripes are arguments, not baked-in constants
    stripes = sum(a.nbytes for a in sargs)
    assert compiled.memory_analysis().argument_size_in_bytes >= stripes


def test_halo_round_compiles_for_four_chips(graphs, topo):
    """The sharded halo round over a described 2x2 mesh has a collective."""
    g = graphs["pagerank"]
    problem = pagerank_problem()
    sr = problem.semiring
    row_update = problem.make_row_update(g)
    sched = make_schedule(g, WORKERS, DELTA, sr)
    plan = make_frontier_plan(sched, 4)
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    fn = frontier_round_ext_fn(
        sched, plan, sr, lambda old, red, rows, q: row_update(old, red, rows), mesh
    )
    block = NamedSharding(mesh, P("data", None, None, None))
    cell = NamedSharding(mesh, P(None, "data", None))
    whole = NamedSharding(mesh, P())
    layout = (block, cell, cell, cell, cell, block, cell, cell, whole, whole)
    args = frontier_plan_args(sched, plan)
    specs = [_spec(a, s) for a, s in zip(args, layout)]
    x = _spec(np.zeros(g.n + 1, np.float32), whole)
    q = _spec(np.zeros((), np.int32), whole)
    compiled = jax.jit(fn).lower(x, q, *specs).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole_plan = sum(np.asarray(a).nbytes for a in args)
    assert per_device < whole_plan  # each chip holds its shard, not the plan


@pytest.mark.xfail(strict=True, raises=NotImplementedError)
def test_fused_round_kernel_lowers_for_v5e(graphs, one_chip):
    """ROADMAP Reach 1: Mosaic refuses the kernel's 1-D frontier gather."""
    g = graphs["pagerank"]
    problem = pagerank_problem()
    sr = problem.semiring
    row_update = problem.make_row_update(g)
    sched = make_schedule(g, WORKERS, DELTA, sr)
    rnd = fused_round_fn_q(
        sched, sr, lambda old, red, rows, q: row_update(old, red, rows),
        interpret=False,
    )
    x = _spec(np.zeros(g.n + 1, np.float32), one_chip)
    q = _spec(np.zeros((), np.int32), one_chip)
    jax.jit(rnd).lower(x, q).compile()


@pytest.mark.xfail(strict=True, raises=NotImplementedError)
def test_fused_halo_step_kernel_lowers_for_v5e(graphs, one_chip):
    """ROADMAP Reach 1: the per-shard halo kernel has the same gather."""
    g = graphs["pagerank"]
    problem = pagerank_problem()
    sr = problem.semiring
    row_update = problem.make_row_update(g)
    sched = make_schedule(g, WORKERS, DELTA, sr)
    plan = make_frontier_plan(sched, 4)
    step = fused_halo_step_fn(
        sr,
        lambda old, red, rows, q: row_update(old, red, rows),
        P_loc=plan.P_loc,
        M=sched.M,
        delta=sched.delta,
        L=plan.L,
        H=plan.H,
        passes=sched.passes,
        interpret=False,
    )
    i32 = np.int32
    args = (
        np.zeros(plan.L, np.float32),
        np.zeros((plan.P_loc, sched.M), i32),
        np.zeros((plan.P_loc, sched.M), np.float32),
        np.zeros((plan.P_loc, sched.M), i32),
        np.zeros((plan.P_loc, sched.delta), i32),
        np.zeros((plan.P_loc, sched.delta), i32),
        np.zeros((plan.P_loc, sched.delta), i32),
        np.zeros(plan.H, i32),
        np.zeros((), i32),
    )
    jax.jit(step).lower(*(_spec(a, one_chip) for a in args)).compile()
