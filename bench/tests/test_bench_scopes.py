"""The scope reduction and the readers of the program's spans, on synthetic traces.

A stand-in for ``jax.profiler.ProfileData`` serves the ``.xplane.pb`` side
(op times and host spans); a small ``trace.json.gz`` beside it serves each
op's ``tf_op``, as the profiler writes them on a TPU.
"""

import gzip
import json
import sys
from types import SimpleNamespace

import pytest

from bench import harness, scopes
from bench import trace_reduce as tr
from bench.metrics import (
    changed_rows_pct,
    gather_ms,
    publish_ms,
    schedule_put_s,
    segment_reduce_ms,
    solve_host_ms,
    stripe_build_s,
)

ROOT = "jit(solve_loop)/while/body/while/body/closed_call"
# (name, start_ns, end_ns, tf_op): one solve of two rounds inside 0..1000 ns
OPS = [
    ("while.1", 150, 850, "jit(solve_loop)/while"),
    ("while.2", 160, 800, "jit(solve_loop)/while/body/while"),
    ("fusion.1", 160, 300, f"{ROOT}/commit.gather/gather"),
    ("fusion.2", 300, 500, f"{ROOT}/commit.segment_reduce/scatter-add"),
    ("fusion.3", 500, 520, f"{ROOT}/commit.publish/scatter"),
    ("fusion.1", 520, 600, f"{ROOT}/commit.gather/gather"),
    ("fusion.2", 600, 780, f"{ROOT}/commit.segment_reduce/scatter-add"),
    ("fusion.3", 780, 800, f"{ROOT}/commit.publish/scatter"),
    ("convert_reduce_fusion", 800, 810, "jit(solve_loop)/while/body/reduce_sum"),
    ("fusion.1", 820, 830, "jit(_extend)/concatenate"),  # same name, other program
    ("copy.1", 860, 870, None),
]
SPANS = [
    ("bench.window", 0, 1000),
    ("bench.draw", 20, 90),
    ("bench.solve", 100, 900),
    ("repro.solve", 110, 890),
]
ROUNDS = 2


def _event(name, start, end):
    return SimpleNamespace(name=name, start_ns=float(start), duration_ns=float(end - start))


def _hlo(name):
    """An op's name as the ``.xplane.pb`` gives it: the instruction's text."""
    return f"%{name} = f32[8]{{0:T(1024)}} {name.split('.')[0]}(f32[8] %p.1)"


def _profile(ops, spans):
    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[_event("jit_solve_loop", 150, 850)]),
        SimpleNamespace(name=tr.OPS_LINE,
                        events=[_event(_hlo(n), s, e) for n, s, e, _ in ops]),
    ])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=[_event(*s) for s in spans]
                        + [_event("PjitFunction(solve_loop)", 120, 140)]),
    ])
    return SimpleNamespace(planes=[host, device])


def _json_trace(ops):
    meta = [
        {"ph": "M", "pid": 7, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name", "args": {"name": tr.OPS_LINE}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/host:CPU"}},
    ]
    events = [
        {"ph": "X", "pid": 7, "tid": 2, "ts": s / 1000, "dur": (e - s) / 1000, "name": n,
         "args": {} if op is None else {"tf_op": f"{op}:", "long_name": _hlo(n)}}
        for n, s, e, op in ops
    ]
    # a module event with a scope-like name on another line must not count
    events.append({"ph": "X", "pid": 7, "tid": 1, "ts": 0.15, "dur": 0.7,
                   "name": "fusion.1", "args": {"tf_op": f"{ROOT}/commit.publish/x"}})
    return {"displayTimeUnit": "ns", "traceEvents": meta + events}


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """Writes a trace like the profiler's; returns a function of (ops, spans)."""
    scopes._reduced.cache_clear()
    monkeypatch.setattr(harness, "TRACE_ROOT", tmp_path)
    import jax.profiler

    def write(ops=OPS, spans=SPANS, json_ops=None):
        d = tmp_path / "cell" / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True, exist_ok=True)
        (d / "vm.xplane.pb").write_bytes(b"")
        with gzip.open(d / "vm.trace.json.gz", "wt") as f:
            json.dump(_json_trace(ops if json_ops is None else json_ops), f)
        monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                            staticmethod(lambda path: _profile(ops, spans)))
        return tmp_path / "cell"

    yield write
    scopes._reduced.cache_clear()


def _run(trace_root, rounds=(ROUNDS,)):
    red = tr.reduce(tr.load(trace_root))
    return harness.Run(
        device_kind="TPU v5 lite", vertices=8, edges=16, setup_s=1.0,
        schedule_build_s=0.5, compile_s=0.1,
        timed=[harness.Solve(i, r, True) for i, r in enumerate(rounds)],
        window_s=1.0, trace=red,
    )


@pytest.mark.parametrize("name, short", [
    ("%fusion.25 = s32[65544]{0:T(1024)S(1)} fusion(s32[8] %a.1), kind=kCustom",
     "fusion.25"),
    ("copy-done.2", "copy-done.2"),
])
def test_short_name(name, short):
    assert scopes.short_name(name) == short


def test_leaves_drop_enclosing_events():
    ops = [(n, s, e) for n, s, e, _ in OPS]
    names = [o[0] for o in scopes.leaves(ops)]
    assert "while.1" not in names and "while.2" not in names
    assert len(names) == len(OPS) - 2
    # touching ops are both leaves; a zero-length op is a leaf
    assert scopes.leaves([("a", 0, 5), ("b", 5, 9), ("c", 9, 9)]) == [
        ("a", 0, 5), ("b", 5, 9), ("c", 9, 9),
    ]


@pytest.mark.parametrize("tf_op, scope", [
    (f"{ROOT}/commit.gather/gather", "commit.gather"),
    (f"{ROOT}/commit.segment_reduce/scatter-min", "commit.segment_reduce"),
    (f"{ROOT}/commit.publish/select_n", "commit.publish"),
    ("jit(solve_loop)/while/body/gather", scopes.UNSCOPED),  # a primitive's own name
    ("commit.gathered/x", scopes.UNSCOPED),
    (None, scopes.UNSCOPED),
    (f"{ROOT}/commit.publish:", "commit.publish"),  # as the TPU trace ends a path
    ("jit(wrapped)/while/body/while:", scopes.UNSCOPED),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_join_takes_tf_op_of_same_op_by_time(trace_dir):
    trace = scopes.load(trace_dir())
    (ops,) = trace.devices
    by_time = {(scopes.short_name(n), s): scope for n, s, _, scope in ops}
    assert by_time[("fusion.1", 160)] == "commit.gather"
    assert by_time[("fusion.1", 820)] == scopes.UNSCOPED  # same name, other program
    assert by_time[("fusion.3", 780)] == "commit.publish"
    assert by_time[("copy.1", 860)] == scopes.UNSCOPED  # no tf_op
    assert {s[0] for s in trace.spans} == {
        "bench.window", "bench.draw", "bench.solve", "repro.solve",
    }


def test_join_leaves_an_op_unscoped_beyond_tolerance(trace_dir):
    shifted = [(n, s + 5000, e + 5000, op) for n, s, e, op in OPS]
    (ops,) = scopes.load(trace_dir(json_ops=shifted)).devices
    assert {scope for *_, scope in ops} == {scopes.UNSCOPED}


def test_scope_totals_and_unscoped(trace_dir):
    red = scopes.reduce(scopes.load(trace_dir()))
    assert red.scope_s["commit.gather"] == pytest.approx((140 + 80) / 1e9)
    assert red.scope_s["commit.segment_reduce"] == pytest.approx((200 + 180) / 1e9)
    assert red.scope_s["commit.publish"] == pytest.approx((20 + 20) / 1e9)
    assert red.scope_s[scopes.UNSCOPED] == pytest.approx((10 + 10 + 10) / 1e9)
    # leaf ops inside the solve span add up to its busy time: no while counted
    assert sum(red.scope_s.values()) == pytest.approx((640 + 30) / 1e9)
    # repro.solve 110..890 holds 780 ns; ops ran 150..850 and 860..870
    assert red.solve_host_s == [pytest.approx(70 / 1e9)]
    assert red.idle_gaps[:2] == [
        ("bench.draw", pytest.approx(150e-9)),
        ("bench.window", pytest.approx(130e-9)),
    ]
    assert ("repro.solve", pytest.approx(10e-9)) in red.idle_gaps


def test_readers_ms_per_round(trace_dir, capsys):
    run = _run(trace_dir())
    assert gather_ms.read(run) == pytest.approx(1e3 * 220e-9 / ROUNDS)
    assert segment_reduce_ms.read(run) == pytest.approx(1e3 * 380e-9 / ROUNDS)
    assert publish_ms.read(run) == pytest.approx(1e3 * 40e-9 / ROUNDS)
    assert solve_host_ms.read(run) == pytest.approx(1e3 * 70e-9)
    err = capsys.readouterr().err
    assert err.count("scopes ms/round") == 1  # reduced once for all readers
    assert "unscoped" in err and "of round_ms" in err and "repro.solve" in err


def test_scoped_share_of_round_ms(trace_dir):
    run = _run(trace_dir())
    round_ms = 1e3 * run.trace.solve_busy_s / ROUNDS
    scoped = sum(m.read(run) for m in (gather_ms, segment_reduce_ms, publish_ms))
    assert scoped / round_ms == pytest.approx(640 / 710)


def test_a_fused_away_scope_reads_zero(trace_dir):
    ops = [o for o in OPS if o[0] != "fusion.3"]
    run = _run(trace_dir(ops=ops))
    assert publish_ms.read(run) == 0.0
    assert gather_ms.read(run) > 0


def test_parent_like_trace_reads_none(trace_dir):
    """No scopes in any op's name, no repro.* span: as a program without them."""
    ops = [(n, s, e, f"jit(solve_loop)/while/body/{n}") for n, s, e, _ in OPS]
    run = _run(trace_dir(ops=ops, spans=[s for s in SPANS if s[0] != "repro.solve"]))
    for m in (gather_ms, segment_reduce_ms, publish_ms, solve_host_ms):
        assert m.read(run) is None


def test_untraced_run_reads_none(trace_dir):
    run = _run(trace_dir())
    run.trace = None
    for m in (gather_ms, segment_reduce_ms, publish_ms, solve_host_ms):
        assert m.read(run) is None


def test_trace_reduce_unchanged_by_repro_spans(trace_dir):
    """The accepted reduction reads only bench.* spans, whatever else is there."""
    with_repro = tr.reduce(tr.load(trace_dir()))
    without = tr.reduce(tr.load(trace_dir(spans=[s for s in SPANS
                                                 if not s[0].startswith("repro.")])))
    assert with_repro == without
    assert all(name.startswith("bench.") for name, _ in with_repro.idle_gaps)


def _span(name, parent, start, end, **attrs):
    from repro.spans import Span

    return Span(name, parent, int(start * 1e9), int(end * 1e9), attrs)


@pytest.fixture
def records(monkeypatch):
    """Replace the program's span record with a list the test fills."""
    from repro import spans

    recs = []
    monkeypatch.setattr(
        spans, "records", lambda name=None: [r for r in recs if name in (None, r.name)]
    )
    return recs


def _solve(rounds, changed, n=100):
    return _span("repro.solve", None, 0, 1, rounds=rounds, changed_rows=changed,
                 row_updates=rounds * n)


def _plain_run(rounds):
    return harness.Run(
        device_kind="TPU v5 lite", vertices=100, edges=16, setup_s=1.0,
        schedule_build_s=0.5, compile_s=0.1,
        timed=[harness.Solve(i, r, True) for i, r in enumerate(rounds)],
        window_s=1.0,
    )


def test_changed_rows_pct_over_the_timed_solves(records):
    records += [_solve(9, 900), _solve(4, 100), _solve(6, 300)]  # warm-up first
    assert changed_rows_pct.read(_plain_run([4, 6])) == pytest.approx(
        100 * 400 / 1000
    )


@pytest.mark.parametrize("stored, timed", [
    ([(4, 100), (6, 300)], [4, 7]),  # rounds differ from the window's
    ([(6, 300)], [4, 6]),  # fewer records than solves
    ([(4, None), (6, 300)], [4, 6]),  # a runner that kept no count
])
def test_changed_rows_pct_none_where_records_do_not_match(records, stored, timed):
    records += [_solve(r, c) for r, c in stored]
    assert changed_rows_pct.read(_plain_run(timed)) is None


def test_set_up_spans(records):
    records += [
        _span("repro.schedule.put", "repro.schedule.build", 1.25, 1.5),
        _span("repro.schedule.build", None, 0.0, 1.5),
        _span("repro.schedule.put", "repro.schedule.build", 3.0, 3.25),
        _span("repro.schedule.build", None, 2.5, 3.25),  # a later δ: not read
    ]
    assert schedule_put_s.read(_plain_run([1])) == pytest.approx(0.25)
    assert stripe_build_s.read(_plain_run([1])) == pytest.approx(1.25)


def test_set_up_readers_none_without_spans(records):
    for m in (schedule_put_s, stripe_build_s, changed_rows_pct):
        assert m.read(_plain_run([3])) is None


def test_program_span_readers_none_without_recorder_module(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    for m in (schedule_put_s, stripe_build_s, changed_rows_pct):
        assert m.read(_plain_run([3])) is None


def test_solve_host_ms_is_the_median_over_solves(monkeypatch):
    red = scopes.Reduction(scope_s={}, solve_host_s=[0.0099, 0.1533, 0.0085],
                           idle_gaps=[])
    monkeypatch.setattr(scopes, "for_run", lambda run: red)
    assert solve_host_ms.read(_plain_run([9, 10, 10])) == pytest.approx(9.9)
