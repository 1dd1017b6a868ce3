"""The trace reduction on a small synthetic trace."""

import pytest

from bench import trace_reduce as tr


def _trace():
    # window 0..100 ns; solve spans 10..50 and 60..95; a draw 50..60
    spans = [
        ("bench.window", 0, 100),
        ("bench.solve", 10, 50),
        ("bench.draw", 50, 60),
        ("bench.solve", 60, 95),
    ]
    ops = [
        ("fusion.1", 12, 30),
        ("fusion.2", 25, 40),  # overlaps fusion.1: busy counts 12..40 once
        ("gather.7", 42, 48),
        ("copy-start.3", 62, 70),
        ("while.1", 70, 94),
        ("fusion.9", -5, 3),  # starts before the window: clipped to 0..3
    ]
    return tr.Trace(devices=[ops], spans=spans)


def test_merge_clips_and_joins():
    assert tr.merge([(5, 10), (8, 12), (20, 30), (-4, 2)], 0, 25) == [
        [0, 2], [5, 12], [20, 25],
    ]


def test_busy_union_and_idle_share():
    red = tr.reduce(_trace())
    # 0..3, 12..40, 42..48, 62..94
    assert red.busy_s == pytest.approx((3 + 28 + 6 + 32) / 1e9)
    assert red.window_s == pytest.approx(100 / 1e9)
    assert red.idle_share == pytest.approx(1 - 69 / 100)
    # inside the solve spans: 12..40, 42..48 and 62..94
    assert red.solve_busy_s == pytest.approx((28 + 6 + 32) / 1e9)


def test_op_totals_sum_by_kind():
    red = tr.reduce(_trace())
    totals = dict(red.device_ops)
    assert totals["fusion"] == pytest.approx((18 + 15 + 3) / 1e9)
    assert totals["while"] == pytest.approx(24 / 1e9)
    assert totals["copy-start"] == pytest.approx(8 / 1e9)
    assert red.device_ops[0][0] == "fusion"  # most time first


def test_gaps_named_by_innermost_span():
    red = tr.reduce(_trace())
    # gaps: 3..12 (window, 9), 40..42 (solve, 2), 48..62 (mid 55: draw, 14),
    # 94..100 (mid 97: window, 6)
    assert red.idle_gaps == [
        ("bench.draw", pytest.approx(14e-9)),
        ("bench.window", pytest.approx(9e-9)),
        ("bench.window", pytest.approx(6e-9)),
        ("bench.solve", pytest.approx(2e-9)),
    ]


def test_busy_is_mean_over_devices():
    t = _trace()
    t.devices.append([("fusion.1", 0, 100)])
    red = tr.reduce(t)
    assert red.busy_s == pytest.approx((69 + 100) / 2 / 1e9)


@pytest.mark.parametrize(
    "name, kind",
    [("fusion.12", "fusion"), ("%gather.3", "gather"), ("copy-done", "copy-done"),
     ("while.1 = f32[] while(...)", "while")],
)
def test_op_kind(name, kind):
    assert tr.op_kind(name) == kind
