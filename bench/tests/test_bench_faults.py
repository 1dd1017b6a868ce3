"""A whole run, chip check skipped, with the timed path broken underneath.

Each fault a one-chip cell can have must turn ``correct`` false: a solve
that returns its state unchanged, half of each round's work left out, and an
answer altered where it is produced.  (The exchange between chips does not
exist on one chip.)  The unbroken run must stay correct.
"""

import dataclasses
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, spec

SPEC = spec.load_spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def _small(cell):
    return dataclasses.replace(
        cell,
        config={**cell.config, "scale": 10},
        traffic={**cell.traffic, "delta": 128, "reference_workers": 2,
                 "check_solves": 4},
    )


def _unchanged(monkeypatch):
    import repro.solve.solver as solver_mod

    real = solver_mod.execute_solve_fn

    def fake(fn, sched, sr, x_ext, *args, **kwargs):
        res = real(fn, sched, sr, x_ext, *args, **kwargs)
        return dataclasses.replace(res, x=np.asarray(x_ext[:-1]))

    monkeypatch.setattr(solver_mod, "execute_solve_fn", fake)


def _half_left_out(monkeypatch):
    import repro.core.engine as engine

    real = engine.build_stripe_schedule

    def fake(graph, bounds, delta, pad_val):
        s = real(graph, bounds, delta, pad_val)
        val = s.val.copy()
        val[:, s.P // 2:, :] = pad_val  # the second half of the workers' edges
        return dataclasses.replace(s, val=val)

    monkeypatch.setattr(engine, "build_stripe_schedule", fake)


def _altered(monkeypatch):
    import repro.solve.solver as solver_mod

    real = solver_mod.execute_solve_fn

    def fake(*args, **kwargs):
        res = real(*args, **kwargs)
        x = np.array(res.x)
        x[np.argmin(x)] += 1  # one answer off where it is produced
        return dataclasses.replace(res, x=x)

    monkeypatch.setattr(solver_mod, "execute_solve_fn", fake)


FAULTS = {"none": None, "unchanged": _unchanged, "half_left_out": _half_left_out,
          "altered": _altered}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_turns_correct_false(monkeypatch, workload, fault):
    monkeypatch.setattr(
        "repro.launch.compile_cache.enable_compile_cache", lambda: "off (test)"
    )
    if FAULTS[fault]:
        FAULTS[fault](monkeypatch)
    cell = _small(spec.resolve(SPEC, workload))
    result = harness.measure(cell, 2**32 + 7, 0.3, False, t_start=time.perf_counter(),
                             require_tpu=False)
    assert result["correct"] is (fault == "none"), result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == set(cell.end_to_end)
    assert result["device"]["platform"] == "cpu" and result["attempted"] >= 1


def test_no_tpu_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 2 and proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_bench_files_alone_exit_nonzero(tmp_path):
    import shutil

    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout == ""
