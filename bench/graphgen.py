"""Device-side graph generation shared by the generators in ``bench/generators``.

A generator draws a directed edge list with ``jax.random`` on the default
device; :func:`symmetric_csr` then does what GAP's builder does for an
undirected graph: add each edge's reverse with the same weight, drop self
loops, sort, and squish duplicates (the lightest weight of a duplicate pair is
kept).  The sort runs on the device; the host only compacts the sorted arrays
and counts degrees.  The result is the pull CSR of
:class:`repro.graphs.formats.CSRGraph`, built directly (no second sort).

Every draw comes from ``--seed`` through :func:`seed_key`, so the same seed
gives the same graph on every platform (threefry is bit-exact across
backends).
"""

from __future__ import annotations

import dataclasses
import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: GAP's SSSP edge lengths: integers drawn uniformly from [1, 255].
GAP_MAX_WEIGHT = 255


@dataclasses.dataclass(frozen=True)
class SymmetricGraph:
    """Host pull CSR of an undirected graph with GAP integer weights.

    ``indptr[u]:indptr[u + 1]`` slices the in-edges of ``u`` (equal to its
    out-edges, the graph being symmetric), sorted by source.
    """

    n: int
    indptr: np.ndarray  # (n + 1,) int64
    indices: np.ndarray  # (E,) int32 source of each in-edge
    weights: np.ndarray  # (E,) int32 in [1, 255], equal on both directions

    @property
    def edges(self) -> int:
        """True directed edges E (each undirected edge counts twice)."""
        return int(self.indices.shape[0])

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)


def seed_key(seed: int, stream: int):
    """A threefry key for ``(seed, stream)``; any non-negative int seed."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32
    )
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def gap_weights(key, m: int):
    """``m`` GAP edge lengths, uniform integers in [1, 255]."""
    return jax.random.randint(key, (m,), 1, GAP_MAX_WEIGHT + 1, dtype=jnp.int32)


@partial(jax.jit, static_argnums=0)
def _symmetric_sorted(n, src, dst, w):
    """Both directions of every edge, sorted by (dst, src, w), with a keep mask.

    Self loops are moved to the sentinel row ``n`` so they sort last and are
    dropped; of a run of equal ``(dst, src)`` only the first (lightest) is
    kept.
    """
    s = jnp.concatenate([src, dst])
    d = jnp.concatenate([dst, src])
    ww = jnp.concatenate([w, w])
    d = jnp.where(s == d, n, d)
    d, s, ww = jax.lax.sort((d, s, ww), num_keys=3)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (d[1:] != d[:-1]) | (s[1:] != s[:-1])]
    )
    return d, s, ww, first & (d < n)


def symmetric_csr(n: int, src, dst, w) -> SymmetricGraph:
    """GAP's symmetrize + squish of a device edge list, as a host pull CSR."""
    d, s, ww, keep = (np.asarray(a) for a in _symmetric_sorted(n, src, dst, w))
    d, s, ww = d[keep], s[keep], ww[keep]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(d, minlength=n), out=indptr[1:])
    return SymmetricGraph(n=n, indptr=indptr, indices=s, weights=ww)


def generate(config: dict, seed: int) -> SymmetricGraph:
    """The graph of ``config`` for ``seed``, by the generator it names."""
    module = importlib.import_module(f"bench.generators.{config['generator']}")
    return module.generate(config, seed)
