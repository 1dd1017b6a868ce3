"""The host references agree with Solver.solve; their controls do not."""

import json

import numpy as np
import pytest

from bench import graphgen, reference
from bench.problems import pagerank, sssp
from bench.spec import BENCH

KRON = {"generator": "kron", "scale": 10, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
TRAFFIC = {
    name: json.loads((BENCH / "traffic" / f"{file}.json").read_text())
    for name, file in (("pagerank", "pagerank"), ("sssp", "sssp-random-sources"))
}


@pytest.fixture(scope="module")
def graph():
    return graphgen.generate(KRON, 3)


def _solve(g, module, x0, delta=128):
    from repro.graphs.formats import CSRGraph
    from repro.solve import Solver

    traffic = TRAFFIC[module.__name__.rsplit(".", 1)[1]]
    cg = CSRGraph(n=g.n, indptr=g.indptr, indices=g.indices,
                  values=module.edge_values(g, traffic))
    res = Solver(cg, module.problem(traffic), n_workers=8, delta=delta).solve(x0)
    assert res.converged
    return res.x


def test_pagerank_reference_agrees_with_solver(graph):
    _, x0 = next(pagerank.draws(graph, TRAFFIC["pagerank"], 0))
    got = _solve(graph, pagerank, x0)
    want = reference.pagerank(graph.n, graph.indptr, graph.indices)
    assert want.min() > 0
    checks = pagerank.compare([got], [want], TRAFFIC["pagerank"])
    value, limit = checks["l1_to_reference"]
    assert value <= limit


def test_sssp_reference_agrees_with_solver(graph):
    draws = sssp.draws(graph, TRAFFIC["sssp"], 2**40 + 1)
    for _ in range(3):
        source, x0 = next(draws)
        got = _solve(graph, sssp, x0)
        want = reference.sssp(graph.n, graph.indptr, graph.indices, graph.weights, source)
        assert want[source] == 0
        assert sssp.compare([got], [want], TRAFFIC["sssp"]) == {"wrong_distances": (0, 0)}


def test_pool_answers_as_the_functions_do(graph):
    source = int(np.flatnonzero(graph.degree)[0])
    with reference.ReferencePool(graph, 2) as pool:
        pr = pool.submit("pagerank").result()
        d = pool.submit("sssp", source=source).result()
    np.testing.assert_array_equal(pr, reference.pagerank(graph.n, graph.indptr, graph.indices))
    np.testing.assert_array_equal(
        d, reference.sssp(graph.n, graph.indptr, graph.indices, graph.weights, source)
    )


def test_pagerank_control_fails_the_limit(graph):
    want = reference.pagerank(graph.n, graph.indptr, graph.indices)
    control = reference.pagerank(graph.n, graph.indptr, graph.indices, control=True)
    value, limit = pagerank.compare([control], [want], TRAFFIC["pagerank"])["l1_to_reference"]
    assert value > limit


def _ring(n, weight):
    """A cycle whose distances run far past bfloat16's exact integers (256)."""
    u = np.arange(n)
    src = np.concatenate([(u - 1) % n, (u + 1) % n])
    dst = np.concatenate([u, u])
    order = np.lexsort((src, dst))
    indptr = np.arange(0, 2 * n + 1, 2, dtype=np.int64)
    w = np.full(2 * n, weight, np.int32)
    return graphgen.SymmetricGraph(n=n, indptr=indptr,
                                   indices=src[order].astype(np.int32), weights=w)


def test_sssp_control_fails_the_limit():
    g = _ring(600, 37)
    want = reference.sssp(g.n, g.indptr, g.indices, g.weights, 0)
    assert want.max() == 300 * 37
    control = reference.sssp(g.n, g.indptr, g.indices, g.weights, 0, control=True)
    (value, limit), = sssp.compare([control], [want], TRAFFIC["sssp"]).values()
    assert value > limit


def test_round_bf16():
    x = np.array([1.0, 257.0, 259.0, 1 / 3, np.inf], np.float32)
    np.testing.assert_array_equal(
        reference.round_bf16(x), np.array([1.0, 256.0, 260.0, 0.333984375, np.inf], np.float32)
    )
