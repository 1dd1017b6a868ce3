"""Pallas TPU kernel: one full engine round (all S commit steps) fused.

This is the production realisation of the paper's thread-local delay buffer:
the extended frontier ``x_ext`` is input/output-aliased in VMEM and every
commit step reads the values committed by the steps before it — chunk compute,
δ-buffer, and flush never leave the chip.  HBM sees each edge stripe exactly
once and the frontier exactly twice (one read in, one write out) per round,
where the XLA round (:func:`repro.core.engine.round_fn`) round-trips the
frontier through HBM on every one of the ``S`` commit steps.

Generalises the retired ``delayed_block.py`` (hardcoded ⊕=+/⊗=× and
PageRank's row update) to the full ``Semiring`` × ``row_update`` family, and
is driven directly by the engine's ``(S, P, M)`` stripe layout — the same
:class:`repro.core.engine.DeviceSchedule` arrays the XLA round consumes, so
``backend="pallas"`` needs no second schedule build:

* grid = ``(S,)`` with ``dimension_semantics=("arbitrary",)`` — commit steps
  execute sequentially, so step ``s`` reads steps ``< s``'s commits (block
  Gauss–Seidel, exactly :func:`repro.core.engine._commit_step`'s order);
* per step the BlockSpecs stage that step's ``(P, M)`` edge stripe through
  VMEM while the frontier and any row-update constants stay VMEM-resident
  (index_map → 0 for the whole grid);
* the kernel body runs the *same* semiring ops as the XLA commit step
  (:func:`repro.core.semiring.edge_products`,
  :func:`~repro.core.semiring.sorted_segment_reduce`, ``row_update``,
  publish scatter), which is what makes the parity bar bit-identical rather
  than merely allclose.

``row_update`` is an arbitrary callable and may close over device arrays
(Jacobi's ``b/diag`` table, a PPR teleport vector).  Pallas kernels cannot
capture traced constants, so the builder traces ``row_update`` to a jaxpr
once, hoists its closure constants into explicit kernel inputs, and
re-evaluates the jaxpr inside the kernel — any engine-compatible row update
runs unmodified.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.core import eval_jaxpr
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring, edge_products, sorted_segment_reduce

__all__ = [
    "fused_halo_step_fn",
    "fused_round_fn",
    "fused_round_fn_q",
    "resolve_interpret",
]

# Pins the grid sequential on TPU: commit step s reads steps < s.
_SEQUENTIAL_GRID = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → compiled on TPU, interpret-mode emulation elsewhere.

    Explicit ``True``/``False`` is honoured as given (validation runs force
    interpretation; TPU unit tests may force compilation).
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _full_spec(shape: tuple) -> pl.BlockSpec:
    """A BlockSpec pinning the whole array VMEM-resident for every grid step."""
    return pl.BlockSpec(shape, lambda s, _nd=len(shape): (0,) * _nd)


def _at_least_1d(leaf):
    arr = jnp.asarray(leaf)
    return arr.reshape((1,)) if arr.ndim == 0 else arr


def _trace_row_update(row_update_q, semiring: Semiring, P, delta, q_avals, feat=()):
    """Trace ``row_update(old, reduced, rows, q)`` and hoist its constants.

    ``feat`` is the frontier's trailing feature shape — ``()`` for the vector
    engine, ``(F,)`` for matrix frontiers — so ``old``/``reduced`` trace at
    the same rank the kernel will feed them.
    """
    closed = jax.make_jaxpr(row_update_q)(
        jax.ShapeDtypeStruct((P, delta) + tuple(feat), semiring.dtype),
        jax.ShapeDtypeStruct((P, delta) + tuple(feat), semiring.dtype),
        jax.ShapeDtypeStruct((P, delta), np.int32),
        *q_avals,
    )
    consts = [jnp.asarray(c) for c in closed.consts]
    return closed.jaxpr, consts


def fused_round_fn_q(
    sched, semiring: Semiring, row_update, *, interpret: bool | None = None
):
    """Return ``(x_ext, q) -> x_ext`` running one full round in one kernel.

    Drop-in for :func:`repro.core.engine.round_fn_q`: same schedule, same
    ``row_update(old, reduced, rows, q)`` contract, bit-identical per round
    (the kernel body applies the identical semiring ops in the identical
    order).  ``q`` is a per-query pytree whose leaves ride along as
    VMEM-resident kernel inputs, so the returned callable vmaps for
    :func:`repro.solve.batch.solve_batch` and iterates inside
    ``lax.while_loop`` for the fused solve path.
    """
    S, P, M, delta = sched.S, sched.P, sched.M, sched.delta
    passes, n_slots = sched.passes, sched.n_slots
    interp = resolve_interpret(interpret)

    def rnd(x_ext, q):
        feat = tuple(jnp.shape(x_ext)[1:])  # () vector, (F,) matrix frontier
        q_leaves, q_tree = jax.tree_util.tree_flatten(q)
        q_avals = [
            jax.ShapeDtypeStruct(jnp.shape(leaf), jnp.result_type(leaf))
            for leaf in q_leaves
        ]

        def row_update_flat(old, reduced, rows, *leaves):
            return row_update(
                old, reduced, rows, jax.tree_util.tree_unflatten(q_tree, leaves)
            )

        jaxpr, consts = _trace_row_update(
            row_update_flat, semiring, P, delta, q_avals, feat
        )
        c_shapes = [c.shape for c in consts]
        c_in = [_at_least_1d(c) for c in consts]
        q_in = [_at_least_1d(leaf) for leaf in q_leaves]
        n_consts, n_q = len(c_in), len(q_in)

        def kernel(*refs):
            # refs = (src, val, dst, rows, row_last, *consts, *q, x_in, x_out);
            # x_in is the alias donor — x_ref below is the persistent VMEM
            # frontier.
            src_ref, val_ref, dst_ref, rows_ref, last_ref = refs[:5]
            c_refs = refs[5 : 5 + n_consts]
            q_refs = refs[5 + n_consts : 5 + n_consts + n_q]
            x_ref = refs[-1]
            src = src_ref[0]  # (P, M) — this commit step's edge stripe
            val = val_ref[0]
            dst = dst_ref[0]
            rows = rows_ref[0]  # (P, delta)
            x = x_ref[...]  # reads every prior step's commits
            contrib = edge_products(semiring, x[src], val, dst)
            reduced = sorted_segment_reduce(
                semiring, contrib, dst, last_ref[0], passes
            )
            old = x[rows]
            c_vals = [c[...].reshape(shape) for c, shape in zip(c_refs, c_shapes)]
            leaves = [r[...].reshape(a.shape) for r, a in zip(q_refs, q_avals)]
            (new,) = eval_jaxpr(jaxpr, c_vals, old, reduced, rows, *leaves)
            # The flush: commit this step's chunks into the VMEM frontier.
            if feat:
                chunk = new.reshape((-1,) + feat).astype(x_ref.dtype)
                x_ref[...] = x.at[rows.reshape(-1)].set(chunk)
            else:
                x_ref[rows.reshape(-1)] = new.reshape(-1).astype(x_ref.dtype)

        stripe = [
            pl.BlockSpec((1, P, M), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, P, M), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, P, M), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, P, delta), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, P, delta), lambda s: (s, 0, 0)),
        ]
        resident = [_full_spec(a.shape) for a in (*c_in, *q_in)]
        return pl.pallas_call(
            kernel,
            grid=(S,),
            in_specs=stripe + resident + [_full_spec((n_slots,) + feat)],
            out_specs=_full_spec((n_slots,) + feat),
            out_shape=jax.ShapeDtypeStruct((n_slots,) + feat, semiring.dtype),
            # x_ext in ↔ out: commits stay visible across sequential steps
            input_output_aliases={5 + n_consts + n_q: 0},
            interpret=interp,
            compiler_params=_SEQUENTIAL_GRID,
        )(
            sched.src,
            sched.val,
            sched.dst_local,
            sched.rows,
            sched.row_last,
            *c_in,
            *q_in,
            x_ext,
        )

    return rnd


def fused_halo_step_fn(
    semiring: Semiring,
    row_update,
    *,
    P_loc: int,
    M: int,
    delta: int,
    L: int,
    H: int,
    passes: int,
    interpret: bool | None = None,
):
    """One owner-computes halo commit step, fused into a single kernel.

    Returns ``(x_loc, src_s, val_s, dst_s, last_s, rows_g_s, rows_loc_s,
    send_s, q) -> (x_loc, send_vals)`` — the per-shard half of one commit step of
    :func:`repro.dist.engine_sharded.frontier_pallas_round_fn`: gather,
    ⊗, per-worker segment-⊕, ``row_update``, the owner-computes publish into
    the shard's ``(L,)`` local frontier (input/output-aliased, so the
    frontier never leaves VMEM inside the step), and the selection of the
    ``(H,)`` boundary rows this commit must ship.  Only the all-gather of
    those boundary rows stays outside the kernel — it is the one part of a
    halo commit that must cross devices, so it is also the only part whose
    intermediates touch HBM.

    Unlike :func:`fused_round_fn_q` the grid holds a single step: shard ``e``
    at step ``s`` reads remote boundary values committed at ``s - 1``, so a
    cross-device exchange must run between commits and an all-``S`` fused
    grid per shard cannot reproduce the reference order.  The engine calls
    this kernel ``S`` times per round under ``lax.fori_loop``, exchanging
    halos between invocations.

    ``last_s`` is the step's ``row_last`` slice and ``passes`` the schedule's
    scan passes (:func:`repro.core.semiring.sorted_segment_reduce`).
    ``rows_loc_s`` are shard-local row slots (dump ``= L - 1``) used for the
    read-modify-write; ``rows_g_s`` are the global row ids ``row_update``
    sees (PPR teleports index ``q`` by global vertex).  ``send_s`` indexes
    the flat ``(P_loc·δ,)`` committed chunk, exactly like the XLA halo
    round's ``send_idx``.
    """
    interp = resolve_interpret(interpret)

    def step(x_loc, src_s, val_s, dst_s, last_s, rows_g_s, rows_loc_s, send_s, q):
        feat = tuple(jnp.shape(x_loc)[1:])  # () vector, (F,) matrix frontier
        q_leaves, q_tree = jax.tree_util.tree_flatten(q)
        q_avals = [
            jax.ShapeDtypeStruct(jnp.shape(leaf), jnp.result_type(leaf))
            for leaf in q_leaves
        ]

        def row_update_flat(old, reduced, rows, *leaves):
            return row_update(
                old, reduced, rows, jax.tree_util.tree_unflatten(q_tree, leaves)
            )

        jaxpr, consts = _trace_row_update(
            row_update_flat, semiring, P_loc, delta, q_avals, feat
        )
        c_shapes = [c.shape for c in consts]
        c_in = [_at_least_1d(c) for c in consts]
        q_in = [_at_least_1d(leaf) for leaf in q_leaves]
        n_consts, n_q = len(c_in), len(q_in)

        def kernel(*refs):
            src_ref, val_ref, dst_ref, last_ref, rg_ref, rl_ref, snd_ref = refs[:7]
            c_refs = refs[7 : 7 + n_consts]
            q_refs = refs[7 + n_consts : 7 + n_consts + n_q]
            # x is aliased input ↔ output 0; send is output 1.
            x_ref, send_ref = refs[-2], refs[-1]
            src = src_ref[...]  # (P_loc, M) — owned + halo reads, all local
            val = val_ref[...]
            dst = dst_ref[...]
            rows_g = rg_ref[...]  # (P_loc, delta) global ids for row_update
            rows_l = rl_ref[...]  # (P_loc, delta) local slots (dump = L - 1)
            x = x_ref[...]
            contrib = edge_products(semiring, x[src], val, dst)
            reduced = sorted_segment_reduce(
                semiring, contrib, dst, last_ref[...], passes
            )
            old = x[rows_l]
            c_vals = [c[...].reshape(shape) for c, shape in zip(c_refs, c_shapes)]
            leaves = [r[...].reshape(a.shape) for r, a in zip(q_refs, q_avals)]
            (new,) = eval_jaxpr(jaxpr, c_vals, old, reduced, rows_g, *leaves)
            chunk = new.reshape((-1,) + feat).astype(x_ref.dtype)
            # Owner-computes publish: commit this shard's chunk in VMEM.
            if feat:
                x_ref[...] = x.at[rows_l.reshape(-1)].set(chunk)
            else:
                x_ref[rows_l.reshape(-1)] = chunk
            # Boundary selection for the halo exchange, also in VMEM.
            send_ref[...] = chunk[snd_ref[...]]

        ins = (
            src_s,
            val_s,
            dst_s,
            last_s,
            rows_g_s,
            rows_loc_s,
            send_s,
            *c_in,
            *q_in,
            x_loc,
        )
        return pl.pallas_call(
            kernel,
            grid=(1,),
            in_specs=[_full_spec(jnp.shape(a)) for a in ins],
            out_specs=[_full_spec((L,) + feat), _full_spec((H,) + feat)],
            out_shape=[
                jax.ShapeDtypeStruct((L,) + feat, semiring.dtype),
                jax.ShapeDtypeStruct((H,) + feat, semiring.dtype),
            ],
            input_output_aliases={len(ins) - 1: 0},
            interpret=interp,
            compiler_params=_SEQUENTIAL_GRID,
        )(*ins)

    return step


def fused_round_fn(
    sched, semiring: Semiring, row_update, *, interpret: bool | None = None
):
    """Return ``x_ext -> x_ext``: the query-free fused round (one kernel)."""
    fn_q = fused_round_fn_q(
        sched,
        semiring,
        lambda old, reduced, rows, q: row_update(old, reduced, rows),
        interpret=interpret,
    )
    dummy = jnp.zeros((), jnp.int32)
    return lambda x_ext: fn_q(x_ext, dummy)
