"""The benchmark's GAP generators: deterministic per seed, GAP's parameters."""

import json

import numpy as np
import pytest

from bench import graphgen
from bench.spec import BENCH

KRON = {"generator": "kron", "scale": 10, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
URAND = {"generator": "urand", "scale": 10, "edge_factor": 16}
BIG_SEED = 2**33 + 12345  # more than 32 bits hold


@pytest.fixture(scope="module")
def graphs():
    return {
        (name, seed): graphgen.generate(cfg, seed)
        for name, cfg in (("kron", KRON), ("urand", URAND))
        for seed in (1, BIG_SEED)
    }


def _edge_set(g):
    dst = np.repeat(np.arange(g.n), g.degree)
    return dst, g.indices.astype(np.int64), g.weights


@pytest.mark.parametrize("name, cfg", [("kron", KRON), ("urand", URAND)])
def test_same_seed_same_graph(graphs, name, cfg):
    again = graphgen.generate(cfg, BIG_SEED)
    g = graphs[(name, BIG_SEED)]
    for field in ("indptr", "indices", "weights"):
        np.testing.assert_array_equal(getattr(again, field), getattr(g, field))
    other = graphs[(name, 1)]
    assert other.edges != g.edges or not np.array_equal(other.indices, g.indices)


@pytest.mark.parametrize("name", ["kron", "urand"])
def test_gap_builder_semantics(graphs, name):
    g = graphs[(name, BIG_SEED)]
    dst, src, w = _edge_set(g)
    assert g.indptr[0] == 0 and g.indptr[-1] == g.edges
    assert not np.any(src == dst)  # no self loops
    key = dst * g.n + src
    assert np.all(np.diff(key) > 0)  # pull order, no duplicates
    assert w.min() >= 1 and w.max() <= graphgen.GAP_MAX_WEIGHT
    # symmetric, with one weight per undirected edge
    order = np.argsort(src * g.n + dst)
    np.testing.assert_array_equal(key, (src * g.n + dst)[order])
    np.testing.assert_array_equal(w, w[order])
    # edge factor 16, symmetrized: up to 2·16·n directed edges, fewer by the
    # duplicates squished (many at kron's small scales)
    m = 16 * g.n
    assert m < g.edges <= 2 * m


def test_kron_is_skewed_and_urand_is_not(graphs):
    kron, urand = graphs[("kron", 1)], graphs[("urand", 1)]
    assert kron.degree.max() > 20 * kron.degree.mean()
    assert urand.degree.max() < 3 * urand.degree.mean()
    assert (kron.degree == 0).sum() > 0.1 * kron.n  # GAP kron leaves many isolated


@pytest.mark.parametrize("file", ["gap-kron20.json", "gap-urand20.json"])
def test_configs_keep_gap_parameters(file):
    cfg = json.loads((BENCH / "configs" / file).read_text())
    assert cfg["edge_factor"] == 16 and cfg["scale"] == 20
    assert cfg["published"]["scale"] == 27 and "scale" in cfg["reduced"]
    if cfg["generator"] == "kron":
        assert (cfg["A"], cfg["B"], cfg["C"]) == (0.57, 0.19, 0.19)
