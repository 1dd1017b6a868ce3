"""Round bytes, the peak table and the roofline share built from them."""

import pytest

from bench import roofline
from bench.harness import Run, Solve
from bench.metrics import device_idle_pct, round_ms, round_roofline, rounds
from bench.trace_reduce import Reduction


def test_bytes_per_round_counts_edges_and_vertices():
    assert roofline.bytes_per_round(0, 0) == 0
    assert roofline.bytes_per_round(10, 3) == 12 * 10 + 8 * 3
    # GAP kron scale 20: ~31.4M edges, 2**20 vertices -> ~385 MB a round
    assert roofline.bytes_per_round(31_403_720, 2**20) == 385_233_248


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert roofline.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        roofline.peak("cpu", "hbm_bytes_per_s")


def _run(trace):
    return Run(
        device_kind="TPU v5 lite", vertices=1000, edges=20_000, setup_s=1.0,
        schedule_build_s=0.1, compile_s=0.2,
        timed=[Solve(0, 4, True), Solve(1, 6, True)], window_s=2.0, trace=trace,
    )


def test_round_metrics_from_the_trace():
    red = Reduction(window_s=2.0, busy_s=1.5, solve_busy_s=1.0, device_ops=[],
                    idle_gaps=[])
    run = _run(red)
    assert round_ms.read(run) == pytest.approx(100.0)  # 1 s over 10 rounds
    assert rounds.read(run) == pytest.approx(5.0)
    assert device_idle_pct.read(run) == pytest.approx(25.0)
    least = roofline.bytes_per_round(20_000, 1000) / 819e9
    assert round_roofline.read(run) == pytest.approx(100 * least / 0.1)


def test_trace_metrics_are_silent_without_a_trace():
    run = _run(None)
    for reader in (round_ms, round_roofline, rounds, device_idle_pct):
        assert reader.read(run) is None
