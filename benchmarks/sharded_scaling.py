"""Dry-run: replicated vs frontier-sharded commit wire across device counts.

Lowers one delayed-async PageRank round on a 2-block *clustered* graph (two
communities, sparse cross edges — the Fig-5 "diagonal" regime) for both
distribution disciplines at every power-of-two mesh width the host exposes,
and counts the per-round commit wire:

* replicated frontier — each commit all-gathers every worker's chunk:
  ``S · P · δ`` elements per round regardless of topology;
* sharded frontier + halo exchange — each commit ships only boundary rows:
  ``S · D · H`` elements per round, collapsing with the edge cut.

Device-count adaptive like ``engine_dryrun``: 8-wide on the CI smoke mesh,
wider wherever more devices exist.

    PYTHONPATH=src python -m benchmarks.sharded_scaling [--scale 14]
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import write_json_atomic

from repro.core.engine import make_schedule, schedule_args
from repro.core.semiring import PLUS_TIMES, edge_products, sorted_segment_reduce
from repro.dist.compat import make_mesh
from repro.dist.engine_sharded import (
    frontier_ef_init,
    frontier_pallas_round_fn,
    frontier_sharded_round_fn,
    input_specs_for_engine,
    make_frontier_plan,
    sharded_round_fn,
)
from repro.graphs.formats import CSRGraph
from repro.graphs.generators import pagerank_values
from repro.kernels.round_block import fused_halo_step_fn
from repro.launch.dryrun import collective_stats

RESULTS = Path(__file__).resolve().parents[1] / "results"
P = 32  # schedule workers (a multiple of every mesh width we run on)


def clustered_graph(
    scale: int, blocks: int = 2, efactor: int = 8, cross: float = 0.02, seed: int = 0
):
    """``blocks`` equal contiguous communities; ``cross`` fraction of edges
    lands in a random *other* community (the Fig-5 diagonal regime)."""
    n = 2**scale
    m = n * efactor
    rng = np.random.default_rng(seed)
    size = n // blocks
    block = rng.integers(0, blocks, m)
    src = rng.integers(0, size, m) + block * size
    dst = rng.integers(0, size, m) + block * size
    flip = rng.random(m) < cross
    shift = rng.integers(1, blocks, m) if blocks > 1 else np.zeros(m, np.int64)
    dst = np.where(flip, (dst + shift * size) % n, dst)
    vals = pagerank_values(n, src, 0.85)
    return CSRGraph.from_edges(n, src, dst, vals, name=f"cluster{blocks}-s{scale}")


def fused_halo_step_gate(sched, plan, row_update_q) -> dict:
    """Per-shard, per-round HBM bytes: fused Pallas halo step vs XLA's.

    Same two accountings as ``engine_dryrun.fused_vs_xla_round_bytes``:
    the fused kernel's traffic is its HBM *contract* — arguments + outputs
    of the compiled call, everything between (gather temps, ⊗ products,
    segment-sum partials) stays in VMEM — while the XLA commit step is
    priced by its own ``cost_analysis``, which includes exactly those
    intermediate round-trips.  Both are one commit step; ``× S`` per round.
    """
    delta, S = sched.delta, sched.S
    P_loc, M, L, H = plan.P_loc, sched.M, plan.L, plan.H
    avals = (
        jax.ShapeDtypeStruct((L,), jnp.float32),
        jax.ShapeDtypeStruct((P_loc, M), jnp.int32),
        jax.ShapeDtypeStruct((P_loc, M), jnp.float32),
        jax.ShapeDtypeStruct((P_loc, M), jnp.int32),
        jax.ShapeDtypeStruct((P_loc, delta), jnp.int32),
        jax.ShapeDtypeStruct((P_loc, delta), jnp.int32),
        jax.ShapeDtypeStruct((P_loc, delta), jnp.int32),
        jax.ShapeDtypeStruct((H,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    passes = sched.passes
    step = fused_halo_step_fn(
        PLUS_TIMES,
        row_update_q,
        P_loc=P_loc,
        M=M,
        delta=delta,
        L=L,
        H=H,
        passes=passes,
    )
    mem = jax.jit(step).lower(*avals).compile().memory_analysis()
    pallas_step = float(mem.argument_size_in_bytes + mem.output_size_in_bytes)

    def xla_step(x, src_s, val_s, dst_s, last_s, rg_s, rl_s, snd_s, q):
        # one commit step of frontier_sharded_round_fn's body, collectives
        # excluded on both sides (the wire is gated separately below)
        contrib = edge_products(PLUS_TIMES, x[src_s], val_s, dst_s)
        reduced = sorted_segment_reduce(PLUS_TIMES, contrib, dst_s, last_s, passes)
        new = row_update_q(x[rl_s], reduced, rg_s, q)
        newv = new.reshape(-1).astype(x.dtype)
        x = x.at[rl_s.reshape(-1)].set(newv, mode="drop", unique_indices=False)
        return x, newv[snd_s]

    xla_c = jax.jit(xla_step).lower(*avals).compile()
    xla_step_b = float(xla_c.cost_analysis().get("bytes accessed", 0.0))
    return {
        "pallas_halo_step_bytes": pallas_step,
        "pallas_halo_round_bytes": S * pallas_step,
        "xla_halo_step_bytes": xla_step_b,
        "xla_halo_round_bytes": S * xla_step_b,
        "fused_halo_hbm_below_xla": bool(
            xla_step_b > 0 and S * pallas_step < S * xla_step_b
        ),
    }


def quantized_wire_gate(sched, plan, mesh, row_update_q, x_loc) -> dict:
    """Halo wire bytes of the fused pallas round at f32 vs int8.

    Counted from the lowered HLO's collectives, so the int8 number is true
    wire cost — s8 boundary rows plus one f32 scale per (shard, commit) —
    not f32 plus bookkeeping.  Per commit the ratio is ``(H + 4) / 4H``,
    i.e. → 1/4 as the boundary grows; the committed gate is ≤ 0.3.
    """
    ef0 = frontier_ef_init(plan)
    tail = (
        plan.src_loc,
        sched.val,
        sched.dst_local,
        sched.rows,
        sched.row_last,
        plan.rows_loc,
        plan.send_idx,
        plan.recv_idx,
        jnp.zeros((), jnp.int32),
    )
    wire = {}
    for dt in ("f32", "int8"):
        rnd = frontier_pallas_round_fn(
            sched, plan, PLUS_TIMES, row_update_q, mesh, axis="data", halo_dtype=dt
        )
        compiled = jax.jit(rnd).lower(x_loc, ef0, *tail).compile()
        wire[dt] = collective_stats(compiled.as_text())["total_bytes"]
    frac = wire["int8"] / wire["f32"] if wire["f32"] else float("nan")
    return {
        "halo_wire_f32_hlo_bytes": wire["f32"],
        "halo_wire_int8_hlo_bytes": wire["int8"],
        "int8_halo_wire_frac_of_f32": frac,
        "int8_halo_wire_le_030": bool(wire["f32"] > 0 and frac <= 0.3),
    }


def _timed_round(compiled, args, repeats: int = 3) -> float:
    out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=int, default=14, help="log2 vertices")
    ap.add_argument("--delta", type=int, default=128)
    ap.add_argument("--cross", type=float, default=0.02)
    ap.add_argument(
        "--blocks",
        type=int,
        default=None,
        help="communities in the clustered graph (default: widest mesh run)",
    )
    ap.add_argument("--timed", action="store_true", help="also time the rounds")
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    widths, w = [], 1
    while w <= min(P, n_dev):
        widths.append(w)
        w *= 2
    blocks = args.blocks or max(2, widths[-1])

    g = clustered_graph(args.scale, blocks=blocks, cross=args.cross)
    sched = make_schedule(g, P, args.delta, PLUS_TIMES)
    tele = np.float32(0.15 / g.n)
    row_update = lambda o, r, w: tele + r
    row_update_q = lambda o, r, w, q: tele + r
    x_ext = jnp.concatenate(
        [jnp.full((g.n,), 1.0 / g.n, jnp.float32), jnp.zeros((1,), jnp.float32)]
    )

    rows = []
    for width in widths:
        mesh = make_mesh((width,), ("data",), devices=jax.devices()[:width])

        rep = sharded_round_fn(sched, PLUS_TIMES, row_update, mesh, axis="data")
        rep_c = jax.jit(rep).lower(*input_specs_for_engine(sched, PLUS_TIMES)).compile()
        rep_coll = collective_stats(rep_c.as_text())

        plan = make_frontier_plan(sched, width)
        halo = frontier_sharded_round_fn(
            sched, plan, PLUS_TIMES, row_update_q, mesh, axis="data"
        )
        halo_args = (
            plan.scatter_x(x_ext),
            plan.src_loc,
            sched.val,
            sched.dst_local,
            sched.rows,
            sched.row_last,
            plan.rows_loc,
            plan.send_idx,
            plan.recv_idx,
            jnp.zeros((), jnp.int32),
        )
        halo_c = jax.jit(halo).lower(*halo_args).compile()
        halo_coll = collective_stats(halo_c.as_text())

        row = {
            "devices": width,
            "delta": sched.delta,
            "commits_per_round": sched.S,
            "replicated_analytic_bytes": plan.replicated_bytes_per_round(4),
            "halo_analytic_bytes": plan.halo_bytes_per_round(4),
            "halo_boundary_rows": plan.boundary_entries_per_round,
            "halo_H": plan.H,
            "halo_L": plan.L,
            "replicated_hlo_bytes": rep_coll["total_bytes"],
            "halo_hlo_bytes": halo_coll["total_bytes"],
        }
        row.update(fused_halo_step_gate(sched, plan, row_update_q))
        if width > 1:  # 1-wide halos are dump-only; wire ratio is meaningless
            row.update(
                quantized_wire_gate(sched, plan, mesh, row_update_q, halo_args[0])
            )
        if args.timed:
            rep_args = (x_ext, *schedule_args(sched))
            row["replicated_round_s"] = _timed_round(rep_c, rep_args)
            row["halo_round_s"] = _timed_round(halo_c, halo_args)
        rows.append(row)
        rep_kib = row["replicated_analytic_bytes"] / 2**10
        print(
            f"D={width:3d}  replicated: analytic={rep_kib:9.1f} KiB "
            f"hlo={row['replicated_hlo_bytes']/2**10:9.1f} KiB   "
            f"halo: analytic={row['halo_analytic_bytes']/2**10:9.1f} KiB "
            f"hlo={row['halo_hlo_bytes']/2**10:9.1f} KiB  (H={plan.H}, L={plan.L})"
        )
        line = (
            f"      fused halo step: pallas={row['pallas_halo_step_bytes']/2**10:.1f}"
            f" KiB vs xla={row['xla_halo_step_bytes']/2**10:.1f} KiB"
        )
        if "int8_halo_wire_frac_of_f32" in row:
            line += f"   int8 wire = {row['int8_halo_wire_frac_of_f32']:.3f}× f32"
        print(line)

    # Where every device owns whole clusters (width ≤ blocks), halo commits
    # must move strictly less than the replicated all-gather.  Wider meshes
    # split inside communities and are reported but not asserted.
    aligned = [r for r in rows if 1 < r["devices"] <= blocks]
    if aligned:
        worst = max(
            r["halo_analytic_bytes"] / r["replicated_analytic_bytes"] for r in aligned
        )
        print(f"halo/replicated commit-wire ratio (worst aligned width): {worst:.3f}")
        assert worst < 1.0, "halo exchange should move strictly less than replication"
    # ISSUE-8 gates, committed as regression-checked booleans: the fused
    # pallas halo step must beat the XLA step's HBM bytes wherever the cost
    # model prices it, and quantizing the boundary rows must shrink the wire
    # to ≤ 0.3× f32 at every multi-device width.
    for r in rows:
        if r["xla_halo_step_bytes"] > 0:
            assert r["fused_halo_hbm_below_xla"], r
        if "int8_halo_wire_le_030" in r:
            assert r["int8_halo_wire_le_030"], r
    write_json_atomic(RESULTS / "sharded_scaling.json", rows)
    return rows


if __name__ == "__main__":
    main()
