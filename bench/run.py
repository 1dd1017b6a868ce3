#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload kron20.sssp --seed 7 --seconds 51 --trace 0

The cells are the ``workloads`` of ``BENCHMARK.json``.  With ``--trace 0``
the last line of standard output holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
solves.  Either way it holds whether the answers matched the host reference,
and each number compared with its limit, which also end standard error.
Exits 2, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for, and 1 where the program is not in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"the program is not in this checkout ({ROOT / 'src' / 'repro'})",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, spec

    cell = spec.resolve(spec.load_spec(), args.workload)
    try:
        result = harness.measure(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except harness.NoChip as err:
        print(f"[bench] {err}", file=sys.stderr)
        return 2
    d = result["device"]
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})",
                    f"{d['platform']}/{d['kind']}/x{d['count']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
