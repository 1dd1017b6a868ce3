"""Resolve a workload of ``BENCHMARK.json`` to the files that define it.

Everything a cell needs is found by name: its configuration file (the
``file`` of its config entry), its traffic file ``bench/traffic/<traffic>.json``,
the generator ``bench/generators/<generator>.py`` the configuration names,
the problem ``bench/problems/<problem>.py`` the traffic names, and a reader
``bench/metrics/<metric>.py`` for each metric reported in the cell.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric names reported with --trace 0
    per_layer: tuple  # metric names reported with --trace 1
    units: dict  # metric name -> unit

    def metrics(self, trace: bool) -> tuple:
        return self.per_layer if trace else self.end_to_end


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The :class:`Cell` of ``workload``; raises if any file is missing."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r} (known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m["name"] for m in spec["end_to_end"] if _applies(m, workload))
    per_layer = tuple(m["name"] for m in spec["per_layer"] if _applies(m, workload))
    needed = [
        root / "bench" / "generators" / f"{config['generator']}.py",
        root / "bench" / "problems" / f"{traffic['problem']}.py",
        *(root / "bench" / "metrics" / f"{m}.py" for m in e2e + per_layer),
    ]
    missing = [str(p.relative_to(root)) for p in needed if not p.is_file()]
    if missing:
        raise FileNotFoundError(f"workload {workload!r} needs {missing}")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=e2e,
        per_layer=per_layer,
        units={m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]},
    )
