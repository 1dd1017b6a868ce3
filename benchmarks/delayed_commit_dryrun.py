"""§Perf cell 3: delayed gradient commit on the multi-pod mesh (granite-8b).

The paper's technique at training scale: pods buffer δ local optimizer steps
before committing the averaged parameter delta over DCN.  We lower the
*local* phase and the *commit* phase separately on the (2,16,16) mesh and
count collective bytes in each HLO, then report the amortised per-step
collective cost

    bytes(δ) = local_bytes + commit_bytes / δ

for δ ∈ {1, 2, 4, 8}, with f32 vs int8 wire compression, against the plain
synchronous-DP baseline (grads all-reduced over the pod axis every step).

Run (needs ~3 compiles at 512 host devices)::

    PYTHONPATH=src python -m benchmarks.delayed_commit_dryrun

With fewer than 512 devices (CI runs 8 fake ones) the sweep drops to smoke
mode automatically: reduced config, small shape, a (2, D/4, 2) mesh — same
HLO structure, CPU-sized compiles.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
from functools import partial
from pathlib import Path

import jax
from jax.sharding import PartitionSpec as P

from benchmarks.common import write_json_atomic

from repro.configs import get_config, get_reduced
from repro.configs.shapes import SHAPES, ShapeSpec
from repro.dist.compat import make_mesh
from repro.dist.delayed_commit import (
    DelayedCommitConfig,
    DelayedCommitState,
    init_delayed_state,
    make_delayed_commit_step,
    pod_prefix_specs,
)
from repro.dist.sharding import tree_param_specs, use_rules
from repro.launch.dryrun import collective_stats, named, rules_for
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_specs
from repro.train.optimizer import AdamW, constant

RESULTS = Path(__file__).resolve().parents[1] / "results"


def smoke_cell():
    """(cfg, shape, mesh) for hosts too small for the production mesh."""
    n_dev = len(jax.devices())
    assert n_dev >= 4 and n_dev % 4 == 0, f"smoke mesh needs 4k devices, got {n_dev}"
    mesh = make_mesh((2, n_dev // 4, 2), ("pod", "data", "model"))
    return get_reduced("granite-8b"), ShapeSpec("train_smoke", "train", 128, 8), mesh


def lower_phase(phase: str, compress: str, smoke: bool):
    if smoke:
        cfg, shape, mesh = smoke_cell()
    else:
        cfg = get_config("granite-8b")
        shape = SHAPES["train_4k"]
        mesh = make_production_mesh(multi_pod=True)
    rules = rules_for(cfg, mesh, "train")
    cc = DelayedCommitConfig(n_pods=2, delta=4, compress=compress)
    opt = AdamW(schedule=constant(3e-4))
    key = jax.random.PRNGKey(0)

    specs, shards = batch_specs(cfg, shape, with_labels=True)
    # batch gains a leading pod axis
    pod_specs = {
        k: jax.ShapeDtypeStruct((2, v.shape[0] // 2) + v.shape[1:], v.dtype)
        for k, v in specs.items()
    }
    # drop "pod" from the inner batch axis mapping
    pod_shards = {}
    for k, s in shards.items():
        inner = tuple(
            tuple(a for a in ax if a != "pod") if isinstance(ax, tuple) else ax
            for ax in s
        )
        pod_shards[k] = P("pod", *inner)

    with use_rules(rules), jax.set_mesh(mesh):
        state_sds = jax.eval_shape(partial(init_delayed_state, cfg, opt, cc), key)
        pspecs = tree_param_specs(state_sds.global_params, rules, mesh)
        podspecs = pod_prefix_specs(pspecs)
        state_spec = DelayedCommitState(
            global_params=pspecs,
            local_delta=podspecs,
            opt_state={"m": podspecs, "v": podspecs, "step": P()},
            step=P(),
        )
        state_sh = named(mesh, state_spec)
        batch_sh = named(mesh, pod_shards, pod_specs)
        step = make_delayed_commit_step(cfg, opt, cc, phase=phase, param_specs=pspecs)
        jitted = jax.jit(
            step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,)
        )
        compiled = jitted.lower(state_sds, pod_specs).compile()
    mem = compiled.memory_analysis()
    coll = collective_stats(compiled.as_text())
    return {
        "phase": phase,
        "compress": compress,
        "collective_bytes": coll["total_bytes"],
        "per_kind": coll["per_kind"],
        "bytes_per_device": int(mem.argument_size_in_bytes + mem.temp_size_in_bytes),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small mesh (auto when <512 devices)")
    args = ap.parse_args(argv)
    smoke = args.smoke or len(jax.devices()) < 512

    rows = {}
    for phase, compress in [("local", "none"), ("commit", "none"), ("commit", "int8")]:
        r = lower_phase(phase, compress, smoke)
        rows[f"{phase}_{compress}"] = r
        print(
            f"{phase:7s} {compress:5s}: coll={r['collective_bytes']/2**30:.2f} GiB "
            f"bytes/dev={r['bytes_per_device']/2**30:.2f} GiB"
        )
    local = rows["local_none"]["collective_bytes"]
    commit = rows["commit_none"]["collective_bytes"] - local
    commit_i8 = rows["commit_int8"]["collective_bytes"] - local
    # The int8 row counts the collectives the HLO actually runs, so since the
    # pod reduction moved into the integer domain this is true wire cost —
    # s8 elements on the DCN all-reduce — not f32 plus extra quant ops.
    i8_frac = commit_i8 / commit if commit else float("nan")
    print(f"\nint8 commit wire = {i8_frac:.3f}× f32 commit wire")
    print("\nAmortised per-step collective bytes (GiB) vs δ:")
    print(f"{'δ':>4s} {'f32 commit':>12s} {'int8 commit':>12s}")
    table = []
    for d in (1, 2, 4, 8):
        f32b = local + commit / d
        i8b = local + commit_i8 / d
        table.append({"delta": d, "f32_gib": f32b / 2**30, "int8_gib": i8b / 2**30})
        print(f"{d:4d} {f32b/2**30:12.2f} {i8b/2**30:12.2f}")
    out = {
        "smoke": smoke,
        "phases": rows,
        "amortised": table,
        "int8_commit_wire_frac_of_f32": i8_frac,
        "int8_commit_wire_below_f32": bool(commit_i8 < commit),
    }
    write_json_atomic(RESULTS / "delayed_commit_dryrun.json", out)
    return out


if __name__ == "__main__":
    main()
