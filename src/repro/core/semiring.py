"""Semiring algebra for pull-style iterative graph algorithms.

A pull update is ``x'[u] = row_update(x[u], ⊕_{v ∈ in(u)} x[v] ⊗ A[v, u])``.
The semiring supplies ⊕, ⊗, the ⊕-identity, and the *annihilating edge value*
used for schedule padding (``x ⊗ pad = ⊕-identity`` for every ``x``), so padded
edges are no-ops.  :func:`edge_products` and :func:`sorted_segment_reduce`
build a commit step's ⊗ and per-row ⊕ from ``mul``, ``add`` and ``zero``
alone, so every semiring gets them.

Frontier "rows" need not be scalars: every op here is shape-generic over
trailing feature axes, so the same semiring drives ``(N,)`` vector frontiers
and ``(N, F)`` matrix frontiers (random-walk-with-restart embeddings, F-class
label propagation).  The contract each op must honor:

* ``mul(frontier_vals, edge_vals)`` — ``frontier_vals`` is ``(...,) + feat``
  while ``edge_vals`` arrives pre-expanded with trailing length-1 axes, so a
  plain broadcasting elementwise op (``*``, saturating ``+``) just works.
* ``add`` — elementwise, broadcasting.

With ``feat = ()`` all of this degenerates to the historical vector engine,
bit for bit.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Semiring",
    "PLUS_TIMES",
    "MIN_PLUS",
    "INT_INF",
    "min_plus_int32",
    "edge_products",
    "sorted_segment_reduce",
]

# Largest "infinity" such that INF ⊗ INF never overflows int32 under min-plus.
INT_INF = np.int32(2**30 - 1)


@dataclasses.dataclass(frozen=True)
class Semiring:
    """A (⊕, ⊗) pair plus the identities the schedule padding relies on."""

    name: str
    dtype: np.dtype
    zero: object  # ⊕ identity
    pad_edge_val: object  # annihilator: x ⊗ pad == zero
    mul: Callable  # ⊗(frontier_vals, edge_vals) -> contributions
    add: Callable  # elementwise ⊕


PLUS_TIMES = Semiring(
    name="plus_times",
    dtype=np.dtype(np.float32),
    zero=np.float32(0.0),
    pad_edge_val=np.float32(0.0),
    mul=lambda x, a: x * a,
    add=lambda a, b: a + b,
)

# min-plus over saturating int32 (paper's SSSP uses 32-bit integers).
MIN_PLUS = Semiring(
    name="min_plus",
    dtype=np.dtype(np.int32),
    zero=INT_INF,
    pad_edge_val=INT_INF,
    mul=lambda x, a: jnp.minimum(x + a, INT_INF),
    add=jnp.minimum,
)


def min_plus_int32() -> Semiring:
    """The saturating-int32 min-plus semiring (kept for API compatibility)."""
    return MIN_PLUS


def _shift(a, k: int, fill):
    """``a`` moved ``k`` slots along axis 1, ``fill`` in the first ``k``.

    One ``pad`` with a negative high edge, which XLA fuses into its consumer
    where a concatenate of a slice is copied out first.
    """
    cfg = [(0, 0, 0)] * a.ndim
    cfg[1] = (k, -k, 0)
    return jax.lax.pad(a, jnp.asarray(fill, a.dtype), cfg)


def edge_products(semiring: Semiring, gathered, val, dst_local):
    """The ⊗ of a commit step, each product rounded before any ⊕ reads it.

    ``gathered`` is ``(P, M) + feat`` (the frontier at each slot's source),
    ``val`` and ``dst_local`` are ``(P, M)``; one edge value broadcasts over
    the feature axes.  A compiler may contract a multiply into the add that
    reads it (XLA's CPU backend always may), and a fused multiply-add rounds
    once where ⊗ then ⊕ round twice.  Whether it does depends on how the
    surrounding program was fused, so a vector and a matrix frontier, or a
    batched and a sharded round, would sum different bits.  An exclusive-or
    of each product's bits with ``dst_local >> 31`` stops that: row ids are
    never negative, so it is zero, but no compiler can prove it, and no add
    of :func:`sorted_segment_reduce` sees a multiply.
    """
    expand = (1,) * (gathered.ndim - val.ndim)
    contrib = semiring.mul(gathered, val.reshape(val.shape + expand))
    if not jnp.issubdtype(contrib.dtype, jnp.floating):
        return contrib  # integer ⊗ and ⊕ are exact
    bits = jnp.dtype(f"uint{8 * contrib.dtype.itemsize}")
    zero = (dst_local >> 31).astype(bits).reshape(dst_local.shape + expand)
    raw = jax.lax.bitcast_convert_type(contrib, bits) ^ zero
    return jax.lax.bitcast_convert_type(raw, contrib.dtype)


def sorted_segment_reduce(semiring: Semiring, contrib, dst_local, row_last, passes):
    """Per-worker segment-⊕ of slots already sorted by destination row.

    ``contrib`` is ``(P, M) + feat``, from :func:`edge_products`, so every
    backend sums the same bits; ``dst_local`` ``(P, M)`` is the row of
    each slot within its worker's cell, non-decreasing along ``M`` (the
    stripes copy edges in CSR order, padding ``δ`` last); ``row_last``
    ``(P, δ)`` is the slot of each row's last edge, ``-1`` for a row with no
    in-edges.  Returns ``(P, δ) + feat``.

    A segmented inclusive scan along ``M`` needs no flags when the ids are
    sorted: pass ``j`` folds in the slot ``2**j`` back wherever it belongs to
    the same row, so after ``passes`` passes, with ``2**passes`` at least the
    longest row, each row's last slot holds the ⊕ of the whole row.  Each pass
    is one elementwise shift, compare, ⊕ and select; the read of the row ends
    is one gather of ``P·δ`` elements.  Padding slots form the row ``δ``,
    which is never read; an empty row reads ``semiring.zero``.
    """
    P, M = dst_local.shape
    feat = tuple(contrib.shape[2:])
    expand = (1,) * len(feat)
    v = contrib
    for j in range(passes):
        k = 1 << j  # below M: a row of 2**(passes - 1) slots or more fits in M
        prev_d = _shift(dst_local, k, -1)
        prev_v = _shift(v, k, semiring.zero)
        same = (dst_local == prev_d).reshape((P, M) + expand)
        v = jnp.where(same, semiring.add(v, prev_v), v)
    last = jnp.maximum(row_last, 0).reshape(row_last.shape + expand)
    out = jnp.take_along_axis(v, last, axis=1)
    return jnp.where(
        (row_last >= 0).reshape(row_last.shape + expand), out, semiring.zero
    ).astype(v.dtype)
