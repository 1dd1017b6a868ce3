"""GAP urand: uniform random (Erdős–Rényi) edges, symmetrized.

``edge_factor · 2**scale`` edges with both endpoints drawn uniformly from the
``2**scale`` vertices, as GAP's ``-u`` generator does.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.graphgen import SymmetricGraph, gap_weights, seed_key, symmetric_csr


@partial(jax.jit, static_argnums=(1, 2))
def _edges(key, scale, edge_factor):
    n = 1 << scale
    m = n * edge_factor
    k_src, k_dst, k_w = jax.random.split(key, 3)
    src = jax.random.randint(k_src, (m,), 0, n, dtype=jnp.int32)
    dst = jax.random.randint(k_dst, (m,), 0, n, dtype=jnp.int32)
    return src, dst, gap_weights(k_w, m)


def generate(config: dict, seed: int) -> SymmetricGraph:
    scale, ef = int(config["scale"]), int(config["edge_factor"])
    src, dst, w = _edges(seed_key(seed, 0), scale, ef)
    return symmetric_csr(1 << scale, src, dst, w)
