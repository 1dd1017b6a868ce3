"""The program's scopes, changed-row count and span record.

* the three commit-step scopes reach the ``op_name`` metadata of the
  compiled jit solve loop, and emit no instruction of their own;
* ``changed_rows`` agrees across the fused loop, the host loop and a numpy
  recount, and stays exact past ``2**32``;
* :mod:`repro.spans` keeps parents, attributes and its bound, writes its
  spans into a profiler trace, and records the Solver's set-up and solves.
"""

import collections
import contextlib
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as engine
from repro import spans
from repro.core.engine import make_solve_fn_q_dyn, schedule_args
from repro.graphs.generators import make_graph
from repro.solve import Solver, pagerank_problem, rwr_embedding_problem, sssp_problem

SCOPES = ("commit.gather", "commit.segment_reduce", "commit.publish")
GRAPHS = {
    "pagerank": make_graph("kron", scale=8, efactor=8, kind="pagerank"),
    "sssp": make_graph("kron", scale=8, efactor=8, kind="sssp"),
}
PROBLEMS = {"pagerank": pagerank_problem, "sssp": sssp_problem}


def _solver(name, **kw):
    return Solver(
        GRAPHS[name], PROBLEMS[name](), n_workers=4, delta=64, min_chunk=16, **kw
    )


def _compiled_loop_text(name):
    solver = _solver(name)
    sched = solver.schedule()
    fn = make_solve_fn_q_dyn(
        sched, solver.problem.semiring, solver._row_update_q, solver.problem.residual
    )
    args = (
        solver._x_ext(None),
        solver.resolve_query(None),
        *schedule_args(sched),
        jnp.float32(solver.tol),
        jnp.int32(solver.max_rounds),
    )
    return jax.jit(fn).lower(*args).compile().as_text()


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def _opcodes(hlo: str) -> collections.Counter:
    """Opcode counts of an HLO module's instructions, metadata left out."""
    lines = (re.sub(r", metadata=\{[^}]*\}", "", line) for line in hlo.splitlines())
    return collections.Counter(
        m.group(1) for m in map(_INSTRUCTION.match, lines) if m is not None
    )


@pytest.mark.parametrize("name", ["pagerank", "sssp"])
def test_scopes_in_compiled_loop_metadata(name):
    names = re.findall(r'op_name="([^"]*)"', _compiled_loop_text(name))
    for scope in SCOPES:
        assert any(scope in n.split("/") for n in names), scope


@pytest.mark.parametrize("name", ["pagerank", "sssp"])
def test_scopes_emit_no_instructions(name, monkeypatch):
    scoped = _opcodes(_compiled_loop_text(name))
    monkeypatch.setattr(jax, "named_scope", lambda _: contextlib.nullcontext())
    bare = _compiled_loop_text(name)
    assert not any(s in bare for s in SCOPES)
    assert scoped == _opcodes(bare)
    assert scoped["scatter"] == 1  # the publish; the segment-⊕ scans, not scatters


def _numpy_changed_rows(solver, rounds):
    rnd = solver.round_callable(backend="host")
    x = solver._x_ext(None)
    total = 0
    for _ in range(rounds):
        x_new = rnd(x)
        total += int(np.sum(np.asarray(x_new)[:-1] != np.asarray(x)[:-1]))
        x = x_new
    return total


@pytest.mark.parametrize("name", ["pagerank", "sssp"])
def test_changed_rows_jit_host_numpy_agree(name):
    solver = _solver(name)
    r_jit = solver.solve(backend="jit")
    r_host = solver.solve(backend="host")
    assert r_jit.rounds == r_host.rounds
    assert r_jit.changed_rows == r_host.changed_rows
    assert r_jit.changed_rows == _numpy_changed_rows(solver, r_jit.rounds)
    assert 0 < r_jit.changed_rows <= r_jit.rounds * GRAPHS[name].n
    if name == "sssp":  # SSSP's residual is itself the rows changed per round
        assert r_host.changed_rows == sum(r_host.residuals)


def test_changed_rows_exact_past_2_32(monkeypatch):
    per_round = 2**32 - 3
    monkeypatch.setattr(
        engine, "changed_rows", lambda x, x_new: jnp.asarray(per_round, jnp.uint32)
    )
    solver = _solver("pagerank")
    res = solver.solve(backend="jit", tol=0.0, max_rounds=5)
    assert res.rounds == 5
    assert res.changed_rows == 5 * per_round  # wraps a uint32 four times


def test_changed_rows_matrix_frontier_counts_rows():
    x = jnp.zeros((5, 3))
    x_new = x.at[1, 0].set(1.0).at[1, 2].set(2.0).at[3, 1].set(1.0).at[4, 0].set(9.0)
    assert int(engine.changed_rows(x, x_new)) == 2  # row 4 is the dump slot


def test_recorder_parents_attributes_and_bound():
    rec = spans.Recorder(maxlen=3)
    with rec.span("outer", kind="a") as attrs:
        with rec.span("inner"):
            pass
        attrs["count"] = 7
    with pytest.raises(RuntimeError):
        with rec.span("failed"):
            raise RuntimeError("recorded all the same")
    inner, outer, failed = rec.records()
    assert (inner.name, inner.parent) == ("inner", "outer")
    assert (outer.parent, outer.attrs) == (None, {"kind": "a", "count": 7})
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert failed.parent is None and failed.seconds >= 0
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.records()] == ["s2", "s3", "s4"]
    assert [s.name for s in rec.records("s3")] == ["s3"]


def test_recorder_parents_are_per_thread():
    rec = spans.Recorder(maxlen=100_000)
    n_threads, per_thread = 16, 200
    barrier = threading.Barrier(n_threads)

    def work(t):
        barrier.wait(timeout=30)
        for _ in range(per_thread):
            with rec.span(f"t{t}"):
                with rec.span(f"t{t}.child"):
                    pass

    threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    got = rec.records()
    assert len(got) == 2 * n_threads * per_thread
    for s in got:
        if s.name.endswith(".child"):
            assert s.parent == s.name[: -len(".child")]
        else:
            assert s.parent is None


def test_spans_in_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("repro.test.outer"):
            with spans.span("repro.test.inner"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {
        e.name
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for e in line.events
    }
    assert {"repro.test.outer", "repro.test.inner"} <= names


def _mark():
    """A fresh span; :func:`_since` returns the records closed after it."""
    with spans.span("test.mark") as attrs:
        attrs["id"] = object()
    return attrs["id"]


def _since(mark):
    got = spans.records()
    i = max(i for i, s in enumerate(got) if s.attrs.get("id") is mark)
    return got[i + 1 :]


def test_solver_records_setup_and_solves():
    mark = _mark()
    solver = _solver("sssp")
    solver.schedule()
    solver.schedule()  # a hit: no span
    res = solver.solve(backend="jit")
    new = _since(mark)
    assert [s.name for s in new] == [
        "repro.schedule.put",
        "repro.schedule.build",
        "repro.solve",
    ]
    put, build, solve = new
    assert put.parent == "repro.schedule.build" and build.parent is None
    assert build.start_ns <= put.start_ns <= put.end_ns <= build.end_ns
    assert solve.attrs == {
        "rounds": res.rounds,
        "changed_rows": res.changed_rows,
        "row_updates": res.rounds * GRAPHS["sssp"].n,
        "columns": 1,
    }
    assert solver.stats["schedule_builds"] == 1


@pytest.mark.parametrize("F", [1, 3])
def test_matrix_solve_records_columns_and_counts_rows_once(F):
    g = GRAPHS["pagerank"]
    solver = Solver(g, rwr_embedding_problem(F), n_workers=4, delta=64, min_chunk=16)
    x = solver._x_ext(None)
    assert x.shape == (g.n + 1, F)
    rnd = solver.round_callable(backend="host")
    res = solver.solve(backend="jit")
    (solve,) = spans.records("repro.solve")[-1:]
    assert solve.attrs["columns"] == F
    assert solve.attrs["row_updates"] == res.rounds * g.n
    changed = entries = 0
    for _ in range(res.rounds):
        x_new = rnd(x)
        ne = np.asarray(x_new)[:-1] != np.asarray(x)[:-1]
        changed += int(ne.any(axis=1).sum())
        entries += int(ne.sum())
        x = x_new
    assert solve.attrs["changed_rows"] == res.changed_rows == changed
    assert 0 < changed <= res.rounds * g.n
    assert entries > changed if F > 1 else entries == changed


def test_schedule_from_persisted_store_is_placed(tmp_path):
    first = _solver("pagerank", cache_dir=tmp_path)
    x_cold = first.solve(backend="jit").x
    mark = _mark()
    warm = _solver("pagerank", cache_dir=tmp_path)
    sched = warm.schedule()
    assert warm.stats["schedule_builds"] == 0
    assert all(isinstance(a, jax.Array) for a in schedule_args(sched))
    np.testing.assert_array_equal(warm.solve(backend="jit").x, x_cold)
    assert [s.name for s in _since(mark)][:2] == [
        "repro.schedule.put",
        "repro.schedule.build",
    ]
