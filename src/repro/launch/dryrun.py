"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all            # full sweep, resumable

Results (memory analysis, cost analysis, per-collective bytes) are written to
``results/dryrun/<arch>__<shape>__<mesh>.json`` — benchmarks/roofline.py reads
them.  Cells that already have a result are skipped (incremental resume).
"""

# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax locks
# the device count on first init, so this must precede every other import.
# setdefault, not assignment: callers (CI smoke-bench, tests) may have pinned
# a smaller device count already.
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALIASES, all_arch_ids, get_config
from repro.configs.shapes import SHAPES, applicable_shapes
from repro.dist.sharding import Rules, tree_param_specs, use_rules
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import decode_step, init_params, prefill
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamW, constant
from repro.train.train_step import TrainState, init_train_state, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8,
    "f32": 4,
    "f16": 2,
    "bf16": 2,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
    "s64": 8,
    "u64": 8,
    "s32": 4,
    "u32": 4,
    "s16": 2,
    "u16": 2,
    "s8": 1,
    "u8": 1,
    "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)


def _bytes_of_type(tstr: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(tstr):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the HLO."""
    per_kind = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        tstr, kind = m.group(1), m.group(2)
        b = _bytes_of_type(tstr)
        per_kind[kind]["count"] += 1
        per_kind[kind]["bytes"] += b
    total = sum(v["bytes"] for v in per_kind.values())
    return {"per_kind": per_kind, "total_bytes": total}


def rules_for(cfg: ModelConfig, mesh, kind: str = "train") -> Rules:
    axes = set(mesh.axis_names)
    model_ok = "model" in axes
    # shard kv cache over heads when they divide the model axis; else over seq
    model_size = mesh.shape["model"] if model_ok else 1
    shard_heads = cfg.n_kv > 0 and model_ok and cfg.n_kv % model_size == 0
    # sequence parallelism for train/prefill: residual-stream activations are
    # sharded over "model" between blocks (Megatron-SP); decode has seq = 1.
    seq_axis = "model" if kind in ("train", "prefill") else None
    return Rules.default(shard_cache_heads=shard_heads, seq_axis=seq_axis)


def _filter_spec(spec, axes: set):
    def f(a):
        if a is None:
            return None
        if isinstance(a, (tuple, list)):
            t = tuple(x for x in a if x in axes)
            return t if t else None
        return a if a in axes else None

    return P(*(f(a) for a in spec))


def named(mesh, spec_tree, sds_tree=None):
    """NamedShardings for ``spec_tree``; unknown axes dropped.

    With ``sds_tree`` given, axes that do not divide the dim size are dropped
    too (jit argument shardings demand exact divisibility — batch=1 decode
    cells, odd vocab sizes, ragged stacks).
    """
    axes = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(s, sds=None):
        spec = _filter_spec(s, axes)
        if sds is not None:
            out = []
            for dim, a in zip(sds.shape, spec):
                total = 1
                for ax in (a if isinstance(a, tuple) else (a,)) if a else ():
                    total *= sizes.get(ax, 1)
                out.append(a if (a is None or dim % total == 0) else None)
            spec = P(*out)
        return NamedSharding(mesh, spec)

    if sds_tree is None:
        return jax.tree.map(one, spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_s, tdef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    flat_d = jax.tree_util.tree_flatten(
        sds_tree, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    )[0]
    return tdef.unflatten([one(s, d) for s, d in zip(flat_s, flat_d)])


def build_cell(arch: str, shape_name: str, multi_pod: bool, kv_quant: bool = False):
    """Lower + compile one cell; returns the result record."""
    import dataclasses

    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant_int8=True)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, mesh, shape.kind)
    key = jax.random.PRNGKey(0)

    kind, arg_specs, arg_shard_specs = input_specs(cfg, shape, rules)
    arg_sh = tuple(named(mesh, s, d) for s, d in zip(arg_shard_specs, arg_specs))

    t0 = time.time()
    with use_rules(rules), jax.set_mesh(mesh):
        if kind == "train":
            from repro.train.optimizer import MixedPrecision

            opt = MixedPrecision(AdamW(schedule=constant(3e-4)))
            state_sds = jax.eval_shape(partial(init_train_state, cfg, opt), key)
            pspecs = tree_param_specs(state_sds.params, rules, mesh)
            state_spec = TrainState(
                params=pspecs,
                opt_state={
                    "master": pspecs,
                    "inner": {"m": pspecs, "v": pspecs, "step": P()},
                },
                step=P(),
            )
            state_sh = named(mesh, state_spec)
            # microbatching keeps big-model activations inside HBM
            n_par = cfg.param_count()
            accum = 8 if n_par > 60e9 else (2 if n_par > 9e9 else 1)
            step_fn = make_train_step(cfg, opt, accum_steps=accum, param_specs=pspecs)
            jitted = jax.jit(
                step_fn,
                in_shardings=(state_sh,) + arg_sh,
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_sds, *arg_specs)
        else:
            params_sds = jax.eval_shape(partial(init_params, cfg), key)
            pspecs = tree_param_specs(params_sds, rules, mesh)
            params_sh = named(mesh, pspecs)
            if kind == "prefill":
                jitted = jax.jit(
                    lambda params, batch: prefill(params, cfg, batch),
                    in_shardings=(params_sh,) + arg_sh,
                )
                lowered = jitted.lower(params_sds, *arg_specs)
            else:  # decode
                cache_sds, tok_sds = arg_specs
                jitted = jax.jit(
                    lambda params, cache, tok: decode_step(params, cfg, cache, tok),
                    in_shardings=(params_sh,) + arg_sh,
                    donate_argnums=(1,),
                )
                lowered = jitted.lower(params_sds, cache_sds, tok_sds)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    mem_rec = {
        k: int(getattr(mem, k, 0) or 0)
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }
    cost_rec = {
        k: float(cost.get(k, 0.0))
        for k in ("flops", "bytes accessed", "transcendentals")
        if cost
    }
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "kind": kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "bytes_per_device": mem_rec["argument_size_in_bytes"]
        + mem_rec["temp_size_in_bytes"],
        "cost": cost_rec,
        "collectives": coll,
        "params": int(cfg.param_count()),
        "active_params": int(cfg.active_param_count()),
    }
    return rec


def run_cell(arch: str, shape_name: str, mesh_name: str, force=False, kv_quant=False):
    RESULTS.mkdir(parents=True, exist_ok=True)
    suffix = "__kvq" if kv_quant else ""
    out = RESULTS / f"{ALIASES[arch]}__{shape_name}__{mesh_name}{suffix}.json"
    if out.exists() and not force:
        print(f"[skip] {out.name}")
        return json.loads(out.read_text())
    try:
        rec = build_cell(
            arch, shape_name, multi_pod=(mesh_name == "multi"), kv_quant=kv_quant
        )
        out.write_text(json.dumps(rec, indent=1))
        print(
            f"[ok]   {out.name}: compile={rec['compile_s']}s "
            f"bytes/dev={rec['bytes_per_device']/2**30:.2f}GiB "
            f"flops={rec['cost'].get('flops', 0):.3g} "
            f"coll={rec['collectives']['total_bytes']/2**30:.2f}GiB"
        )
        return rec
    except Exception as e:  # noqa: BLE001 — sweep must record failures and continue
        err = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc()[-2000:],
        }
        fail = RESULTS / f"FAILED__{ALIASES[arch]}__{shape_name}__{mesh_name}.json"
        fail.write_text(json.dumps(err, indent=1))
        print(f"[FAIL] {arch} {shape_name} {mesh_name}: {err['error']}")
        return err


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--kv-quant", action="store_true", help="int8 KV cache (§Perf)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch in all_arch_ids():
            cfg = get_config(arch)
            for shape_name in applicable_shapes(cfg.family):
                for m in meshes:
                    run_cell(arch, shape_name, m, force=args.force)
    else:
        assert args.arch and args.shape
        for m in meshes:
            run_cell(args.arch, args.shape, m, force=args.force, kv_quant=args.kv_quant)


if __name__ == "__main__":
    main()
