"""GAP kron: Graph500 Kronecker (RMAT) edges, ids permuted, symmetrized.

Each of ``edge_factor · 2**scale`` edges picks one quadrant per bit level
with probabilities A, B, C and 1 - A - B - C (GAP and Graph500: .57, .19, .19,
.05); vertex ids are then permuted so degree does not follow id, as GAP does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.graphgen import SymmetricGraph, gap_weights, seed_key, symmetric_csr


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _edges(key, scale, edge_factor, a, b, c):
    n = 1 << scale
    m = n * edge_factor
    k_bits, k_perm, k_w = jax.random.split(key, 3)

    def level(bit, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_bits, bit), (m,))
        src_bit = r >= a + b
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        return (
            src | (src_bit.astype(jnp.int32) << bit),
            dst | (dst_bit.astype(jnp.int32) << bit),
        )

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    return perm[src], perm[dst], gap_weights(k_w, m)


def generate(config: dict, seed: int) -> SymmetricGraph:
    scale, ef = int(config["scale"]), int(config["edge_factor"])
    a, b, c = (float(config[k]) for k in ("A", "B", "C"))
    src, dst, w = _edges(seed_key(seed, 0), scale, ef, a, b, c)
    return symmetric_csr(1 << scale, src, dst, w)
