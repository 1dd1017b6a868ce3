"""Every workload of BENCHMARK.json resolves to its files, by name."""

import importlib
import json
import re

import pytest

from bench import spec

SPEC = spec.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = spec.resolve(SPEC, workload)
    assert cell.chips in (1, 4)
    assert cell.config["generator"]
    assert cell.traffic["problem"]
    assert "setup_s" in cell.end_to_end
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for name in cell.end_to_end + cell.per_layer:
        assert callable(importlib.import_module(f"bench.metrics.{name}").read)
    for part in ("edge_values", "problem", "draws", "reference", "compare"):
        problem = importlib.import_module(f"bench.problems.{cell.traffic['problem']}")
        assert callable(getattr(problem, part))
    generator = importlib.import_module(f"bench.generators.{cell.config['generator']}")
    assert callable(generator.generate)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.resolve(SPEC, "no.such.cell")


def test_missing_reader_is_refused():
    broken = json.loads(json.dumps(SPEC))
    broken["per_layer"].append(
        {"name": "no_reader_here", "unit": "s", "better": "lower",
         "source": "host_clock", "layer": "device", "moves": "solve_s"}
    )
    with pytest.raises(FileNotFoundError, match="no_reader_here"):
        spec.resolve(broken, WORKLOADS[0])


def test_entries_keep_the_benchmark_contract():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    root = spec.ROOT
    for p in SPEC["paths"]:
        assert (root / p).is_dir()
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (root / c["file"]).is_file()
        assert c["file"].startswith(tuple(p.rstrip("/") + "/" for p in SPEC["paths"]))
        assert all(NAME.match(k) for k in c["reduced"])
        on_file = json.loads((root / c["file"]).read_text())
        assert set(c["reduced"]) <= set(on_file["reduced"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    assert "setup_s" in e2e
