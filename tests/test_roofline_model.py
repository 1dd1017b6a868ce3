"""Validate the trip-count-aware analytic FLOP model (benchmarks §Roofline).

XLA's ``cost_analysis()`` counts loop bodies once, so the roofline uses an
analytic model of the compiled program.  Here we compile configurations with
NO loops (unrolled layers, single-tile attention) where ``cost_analysis`` is
trustworthy, and check the model agrees.
"""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, ".")  # benchmarks is a top-level package in the repo
from benchmarks.model_costs import cell_cost
from repro.configs.shapes import ShapeSpec
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamW, constant
from repro.train.train_step import init_train_state, make_train_step

B, S = 2, 64

CFG = ModelConfig(
    name="val",
    family="dense",
    n_layers=2,
    d_model=128,
    n_heads=4,
    n_kv=2,
    d_ff=256,
    vocab=512,
    scan_layers=False,  # no layer loop
    remat=True,
    q_chunk=S,  # single attention tile → map/scan trip count 1
    kv_chunk=S,
    attn_schedule="masked",
)


def test_xla_counts_loop_bodies_once():
    """The premise: scanned matmuls under-report by the trip count."""

    def f_scan(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=10)[0]

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f_scan).lower(x, w).compile()
    one_matmul = 2 * 128**3
    assert c.cost_analysis()["flops"] < 2 * one_matmul  # not 10×


def test_train_flops_model_matches_unrolled_compile():
    opt = AdamW(schedule=constant(1e-3))
    state = jax.eval_shape(
        lambda k: init_train_state(CFG, opt, k), jax.random.PRNGKey(0)
    )
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    step = make_train_step(CFG, opt)
    compiled = jax.jit(step).lower(state, batch).compile()
    hlo_flops = compiled.cost_analysis()["flops"]
    shape = ShapeSpec("val", "train", S, B)
    model = cell_cost(CFG, shape).flops
    ratio = model / hlo_flops
    # the analytic model should land within 2× of a loop-free compile
    assert 0.5 < ratio < 2.0, (model, hlo_flops)


def test_prefill_flops_model_matches():
    from repro.models import init_params, prefill

    params = jax.eval_shape(lambda k: init_params(CFG, k), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    compiled = (
        jax.jit(lambda p, b: prefill(p, CFG, b)).lower(params, batch).compile()
    )
    hlo_flops = compiled.cost_analysis()["flops"]
    shape = ShapeSpec("val", "prefill", S, B)
    model = cell_cost(CFG, shape).flops
    ratio = model / hlo_flops
    assert 0.4 < ratio < 2.5, (model, hlo_flops)
