#!/usr/bin/env python3
"""Read the control of a cell's comparison at the cell's own size.

    python3 bench/control.py --workload kron20.sssp --seeds 11 12 13

The control is the host reference computed one precision lower
(``bench/reference.py``) put in the program's place: for each seed it builds
the cell's graph as a run does, draws the inputs of the run's first timed
solves, and compares the control's answers with the reference's by the
cell's own numbers and limits.  It prints one JSON line per seed; a sound
control reads ``"correct": false``.  The benchmark's runs never do this.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--solves", type=int, default=4, help="timed solves to compare")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import importlib

    from bench import graphgen, spec
    from bench.reference import ReferencePool

    cell = spec.resolve(spec.load_spec(), args.workload)
    problem = importlib.import_module(f"bench.problems.{cell.traffic['problem']}")
    for seed in args.seeds:
        sym = graphgen.generate(cell.config, seed)
        draws = problem.draws(sym, cell.traffic, seed)
        next(draws)  # the warm-up solve's input
        labels = [next(draws)[0] for _ in range(args.solves)]
        distinct = list(dict.fromkeys(labels))
        workers = max(1, min(2 * len(distinct), int(cell.traffic["reference_workers"])))
        with ReferencePool(sym, workers) as pool:
            ref = {x: problem.reference(pool, x, cell.traffic, False) for x in distinct}
            ctl = {x: problem.reference(pool, x, cell.traffic, True) for x in distinct}
            ref = {x: f.result() for x, f in ref.items()}
            ctl = {x: f.result() for x, f in ctl.items()}
        checks = problem.compare([ctl[x] for x in labels], [ref[x] for x in labels],
                                 cell.traffic)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": "bf16",
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
