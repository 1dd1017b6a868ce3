"""Dry-run roofline of the paper's own engine on the production mesh.

Lowers one delayed-async PageRank round (P = 256 schedule workers, sharded
over however many devices the host exposes — 256-wide on the production
mesh, 8-wide on the CI smoke run) for sync / delayed / async schedules on a
kron graph, and counts the flush all-gather bytes — the TPU realisation of
the paper's Table-I flush counts.

Each row also carries the kernel datapoint: per-round HBM bytes of the
fused Pallas round (:func:`repro.core.engine.round_fn_pallas` — edge stripes
read once, frontier read+written once, everything else VMEM-resident)
against the XLA round, whose every commit step round-trips the frontier
through HBM (``cost_analysis`` of one compiled commit step × S; XLA's
``cost_analysis`` counts loop bodies once, so the full-round number would
undercount — see ``benchmarks/model_costs.py``).

    PYTHONPATH=src python -m benchmarks.engine_dryrun [--scale 19]
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
from functools import partial
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import write_json_atomic

from repro.core.engine import (
    _commit_step,
    make_schedule,
    round_fn_pallas,
    schedule_args,
)
from repro.core.semiring import PLUS_TIMES
from repro.dist.compat import make_mesh
from repro.dist.engine_sharded import input_specs_for_engine, sharded_round_fn
from repro.graphs.generators import make_graph
from repro.launch.dryrun import collective_stats

RESULTS = Path(__file__).resolve().parents[1] / "results"
ICI_BW = 50e9
P = 256  # schedule workers (a multiple of every mesh width we run on)


def fused_vs_xla_round_bytes(sched, row_update) -> dict:
    """Per-round HBM bytes: the fused Pallas round vs the XLA round.

    Three numbers, two accountings:

    * ``pallas_round_bytes`` — the fused kernel's HBM *contract*: by
      BlockSpec construction its traffic is exactly operands + result (edge
      stripes streamed once, frontier in + out once, commits stay in VMEM),
      measured as the compiled call's argument + output bytes.
    * ``xla_round_model_bytes`` — the XLA round under the *same* contract
      accounting: the S steps together also stream the stripes once, but
      each step re-reads and re-writes the frontier through HBM, so the
      frontier term is ``2·S·F`` instead of ``2·F``.  This is the
      apples-to-apples line the S>1 assertion uses — the fusion win is
      exactly ``2·(S−1)·F`` of frontier traffic.
    * ``xla_commit_step_bytes`` / ``xla_round_bytes`` — XLA's own
      ``cost_analysis`` of one compiled commit step (× S for the round).
      This includes intermediate-buffer traffic (gather/segment-sum temps
      the kernel keeps in VMEM), so it sits above the contract model; kept
      as the measured upper line.
    """
    x_ext = jax.ShapeDtypeStruct((sched.n_slots,), PLUS_TIMES.dtype)
    stripes = schedule_args(sched)
    stripe_avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in stripes)

    # The stripe arrays are explicit arguments on both sides (rather than
    # compiled-in constants) so both measurements count the edge traffic.
    def with_stripes(fn_of_sched):
        def wrapped(x, src, val, dst, rows, row_last):
            s = dataclasses.replace(
                sched, src=src, val=val, dst_local=dst, rows=rows, row_last=row_last
            )
            return fn_of_sched(s)(x)

        return jax.jit(wrapped).lower(x_ext, *stripe_avals).compile()

    step = with_stripes(
        lambda s: partial(
            _commit_step, 0, sched=s, semiring=PLUS_TIMES, row_update=row_update
        )
    )
    step_bytes = float(step.cost_analysis().get("bytes accessed", 0.0))
    fused = with_stripes(lambda s: round_fn_pallas(s, PLUS_TIMES, row_update))
    mem = fused.memory_analysis()
    pallas_bytes = float(mem.argument_size_in_bytes + mem.output_size_in_bytes)
    frontier_bytes = np.dtype(PLUS_TIMES.dtype).itemsize * sched.n_slots
    stripe_bytes = sum(int(a.size) * a.dtype.itemsize for a in stripes)
    model_bytes = stripe_bytes + 2 * sched.S * frontier_bytes
    return {
        "xla_commit_step_bytes": step_bytes,
        "xla_round_bytes": sched.S * step_bytes,
        "xla_round_model_bytes": model_bytes,
        "pallas_round_bytes": pallas_bytes,
        "fused_traffic_ratio": pallas_bytes / max(model_bytes, 1),
        # the frontier term alone: S HBM round-trips vs exactly one
        "xla_frontier_bytes_per_round": 2 * sched.S * frontier_bytes,
        "pallas_frontier_bytes_per_round": 2 * frontier_bytes,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=19, help="kron graph scale")
    args = ap.parse_args(argv)

    g = make_graph("kron", scale=args.scale, efactor=8, kind="pagerank")
    n = g.n
    tele = np.float32(0.15 / n)
    row_update = lambda o, r, w: tele + r
    # largest power-of-two mesh width the host supports (always divides P)
    n_dev = len(jax.devices())
    width = 1
    while width * 2 <= min(P, n_dev):
        width *= 2
    mesh = make_mesh((width,), ("data",), devices=jax.devices()[:width])
    rows = []
    for mode, delta in [("async", None), ("delayed", 512), ("sync", None)]:
        sched = make_schedule(g, P, delta, PLUS_TIMES, mode=mode)
        rnd = sharded_round_fn(sched, PLUS_TIMES, row_update, mesh, axis="data")
        specs = input_specs_for_engine(sched, PLUS_TIMES)
        compiled = jax.jit(rnd).lower(*specs).compile()
        coll = collective_stats(compiled.as_text())
        flush_bytes = sched.S * P * sched.delta * 4  # analytic per round
        kernel = fused_vs_xla_round_bytes(sched, row_update)
        if sched.S > 1:
            # the whole point of the fusion: edge stripes once + frontier
            # once beats S frontier round-trips (same contract accounting)
            assert kernel["pallas_round_bytes"] < kernel["xla_round_model_bytes"], (
                kernel
            )
            if kernel["xla_commit_step_bytes"] > 0:  # cost model may omit bytes
                assert kernel["pallas_round_bytes"] < kernel["xla_round_bytes"], kernel
        rows.append(
            {
                "mode": mode,
                "delta": sched.delta,
                "commits_per_round": sched.S,
                "mesh_width": width,
                "hlo_collective_bytes": coll["total_bytes"],
                "analytic_flush_bytes": flush_bytes,
                "flush_time_ms": flush_bytes / (P * ICI_BW) * 1e3
                + sched.S * 1e-3,  # + α=1µs latency per commit
                **kernel,
            }
        )
        print(
            f"{mode:8s} δ={sched.delta:6d} commits/round={sched.S:4d} "
            f"HLO coll={coll['total_bytes']/2**20:8.2f} MiB "
            f"flush-term≈{rows[-1]['flush_time_ms']:.3f} ms/round  "
            f"round HBM: pallas={kernel['pallas_round_bytes']/2**20:7.2f} MiB "
            f"vs xla model={kernel['xla_round_model_bytes']/2**20:7.2f} MiB "
            f"({kernel['fused_traffic_ratio']:.2f}x, "
            f"frontier 1/{sched.S} of the XLA round's)"
        )
    write_json_atomic(RESULTS / "engine_dryrun.json", rows)
    return rows


if __name__ == "__main__":
    main()
