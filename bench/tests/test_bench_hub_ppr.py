"""The hub-PPR cell's pieces: hub rule, reference, control, comparison, roofline."""

import json

import numpy as np
import pytest

from bench import graphgen, hub_reference, roofline, scopes
from bench.harness import Run, Solve
from bench.metrics import matrix_roofline
from bench.problems import hub_ppr
from bench.reference import ReferencePool
from bench.spec import BENCH
from bench.trace_reduce import Reduction

TRAFFIC = json.loads((BENCH / "traffic" / "hub-ppr16.json").read_text())
KRON = {"generator": "kron", "scale": 10, "edge_factor": 16, "A": 0.57, "B": 0.19,
        "C": 0.19}


@pytest.fixture(scope="module")
def graph():
    return graphgen.generate(KRON, 2**31 + 5)


def test_hubs_are_top_degree_ties_to_lower_id():
    deg = np.array([3, 7, 0, 7, 5, 7, 1])
    indptr = np.concatenate([[0], np.cumsum(deg)])
    np.testing.assert_array_equal(hub_reference.hubs(indptr, 4), [1, 3, 5, 4])
    np.testing.assert_array_equal(hub_reference.hubs(indptr, 1), [1])
    with pytest.raises(ValueError):
        hub_reference.hubs(indptr, 8)


def _solve(g, traffic):
    from repro.graphs.formats import CSRGraph
    from repro.solve import Solver

    cg = CSRGraph(n=g.n, indptr=g.indptr, indices=g.indices,
                  values=hub_ppr.edge_values(g, traffic))
    solver = Solver(cg, hub_ppr.problem(traffic), n_workers=8, delta=128)
    _, x0 = next(hub_ppr.draws(g, traffic, 0))
    res = solver.solve(x0)
    assert res.converged
    return res.x


def test_limit_passes_the_program_and_fails_the_control(graph):
    hubs = hub_reference.hubs(graph.indptr, TRAFFIC["columns"])
    want = hub_reference.ppr(graph.n, graph.indptr, graph.indices, hubs, threads=2)
    np.testing.assert_allclose(want.sum(axis=0), 1.0, atol=1e-8)  # no dangling mass
    got = _solve(graph, TRAFFIC)
    assert got.shape == want.shape == (graph.n, 16)
    (value, limit), = hub_ppr.compare([got], [want], TRAFFIC).values()
    assert value <= limit == 5.7e-4
    control = hub_reference.ppr(graph.n, graph.indptr, graph.indices, hubs,
                                control=True, threads=2)
    (value, limit), = hub_ppr.compare([control], [want], TRAFFIC).values()
    assert value > limit


def test_pool_answers_as_the_function_does(graph):
    traffic = {**TRAFFIC, "columns": 3}
    with ReferencePool(graph, 2) as pool:
        got = hub_ppr.reference(pool, "hubs", traffic, False).result()
    hubs = hub_reference.hubs(graph.indptr, 3)
    np.testing.assert_array_equal(
        got, hub_reference.ppr(graph.n, graph.indptr, graph.indices, hubs, threads=2)
    )


def test_matrix_bytes_at_one_column_are_the_scalar_round():
    for e, n in ((0, 0), (10, 3), (31_403_720, 2**20)):
        assert matrix_roofline.bytes_per_round(e, n, 1) == roofline.bytes_per_round(e, n)
    assert matrix_roofline.bytes_per_round(10, 3, 16) == 8 * 10 + 4 * 16 * 10 + 8 * 16 * 3


def _run():
    red = Reduction(window_s=2.0, busy_s=1.5, solve_busy_s=1.0, device_ops=[],
                    idle_gaps=[])
    return Run(device_kind="TPU v5 lite", vertices=1000, edges=20_000, setup_s=1.0,
               schedule_build_s=0.1, compile_s=0.2,
               timed=[Solve("hubs", 4, True), Solve("hubs", 6, True)], window_s=2.0,
               trace=red)


class _Span:
    def __init__(self, rounds, **attrs):
        self.attrs = {"rounds": rounds, **attrs}


@pytest.mark.parametrize("columns,expect", [(16, True), (None, False)])
def test_matrix_roofline_reads_the_solves_columns(monkeypatch, columns, expect):
    attrs = {} if columns is None else {"columns": columns}
    records = [_Span(9, columns=1), _Span(4, **attrs), _Span(6, **attrs)]
    monkeypatch.setattr(scopes, "program_spans", lambda name: records)
    got = matrix_roofline.read(_run())
    if not expect:
        assert got is None
        return
    least = matrix_roofline.bytes_per_round(20_000, 1000, 16) / 819e9
    assert got == pytest.approx(100 * least / 0.1)  # 1 s of device time over 10 rounds
