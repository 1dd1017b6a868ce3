"""Hub personalized PageRank: an ``(n, F)`` solve toward F chosen seeds.

* the matrix solve of ``rwr_embedding_problem`` restarted at the F hubs
  (``bench/problems/hub_ppr.py``) on seeded GAP kron graphs agrees with the
  float64 reference (``bench/hub_reference.py``) within the engine's own L1
  bound, for F in {4, 16} and two values of δ;
* at F = 1 it is bit for bit ``ppr_problem``'s solve toward the same seed;
* the hub problem's default restart is ``rwr_restart`` at the hubs, and
  otherwise the problem is ``rwr_embedding_problem``'s.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # the bench package
from bench import graphgen, hub_reference  # noqa: E402
from bench.problems import hub_ppr  # noqa: E402

from repro.graphs.formats import CSRGraph  # noqa: E402
from repro.solve import (  # noqa: E402
    Solver,
    ppr_problem,
    ppr_teleport,
    rwr_embedding_problem,
    rwr_restart,
)

DAMPING, TOL = 0.85, 1e-4
BOUND = TOL * DAMPING / (1 - DAMPING)  # the engine's L1 error bound at its tolerance
KRON = {"generator": "kron", "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def _traffic(F):
    return {"columns": F, "damping": DAMPING, "tol": TOL}


def _kron(scale):
    g = graphgen.generate({**KRON, "scale": scale}, 2**33 + scale)
    deg = np.bincount(g.indices, minlength=g.n)
    values = (DAMPING / np.maximum(deg[g.indices], 1)).astype(np.float32)
    return g, CSRGraph(n=g.n, indptr=g.indptr, indices=g.indices, values=values)


@pytest.mark.parametrize("delta", [128, 1024])
@pytest.mark.parametrize("F", [4, 16])
@pytest.mark.parametrize("scale", [10, 12])
def test_hub_solve_matches_reference(scale, F, delta):
    sym, g = _kron(scale)
    res = Solver(g, hub_ppr.problem(_traffic(F)), n_workers=8, delta=delta).solve(
        np.full((g.n, F), 1.0 / g.n, np.float32)
    )
    assert res.converged and res.x.shape == (g.n, F)
    hubs = hub_reference.hubs(sym.indptr, F)
    want = hub_reference.ppr(sym.n, sym.indptr, sym.indices, hubs, DAMPING)
    per_column = np.abs(res.x.astype(np.float64) - want).sum(axis=0)
    assert per_column.sum() <= BOUND
    assert np.all(res.x[hubs, np.arange(F)] > res.x.mean(axis=0))  # mass at the hub


def test_hub_f1_bit_identical_to_ppr():
    _, g = _kron(10)
    (hub,) = hub_reference.hubs(g.indptr, 1)
    x0 = np.full(g.n, 1.0 / g.n, np.float32)
    vec = Solver(g, ppr_problem(DAMPING, TOL), n_workers=8, delta=128).solve(
        x0, q=ppr_teleport(g, [hub], DAMPING)[0]
    )
    mat = Solver(g, rwr_embedding_problem(1, DAMPING, TOL), n_workers=8, delta=128).solve(
        x0.reshape(-1, 1), q=rwr_restart(g, [hub], DAMPING)
    )
    assert mat.rounds == vec.rounds
    assert np.array_equal(mat.x[:, 0], vec.x)
    assert mat.changed_rows == vec.changed_rows


def test_hub_problem_restarts_at_the_hubs():
    _, g = _kron(10)
    problem = hub_ppr.problem(_traffic(3))
    np.testing.assert_array_equal(
        problem.default_query(g), rwr_restart(g, hub_reference.hubs(g.indptr, 3), DAMPING)
    )
    base = rwr_embedding_problem(3, DAMPING, TOL)
    assert (problem.feature_dim, problem.tol, problem.takes_query) == (
        base.feature_dim, base.tol, base.takes_query
    )
