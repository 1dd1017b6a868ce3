"""Content-addressed cache keys: what makes a persisted entry *safe* to reuse.

The store never trusts a path: every namespace is derived from the content it
caches results for, so a stale or mismatched entry is a **miss**, never a
wrong answer.  A solver namespace hashes together

* the **graph content** (indptr / indices / values bytes — the schedule graph,
  i.e. after any ``Problem.edge_values`` override);
* the **problem fingerprint** — name, tolerance, semiring, and a digest of the
  row-update's *traced jaxpr including its closure constants* (so two Jacobi
  problems with different right-hand sides never share executables);
* the solver shape knobs (``n_workers``, ``partition_method``, ``min_chunk``);
* the solver's effective ``tol``/``max_rounds`` (constructor overrides
  applied), so different convergence regimes never share a δ-model;
* the **environment** (cache format, repro / jax / numpy versions, and the
  platform and ``device_kind`` of device 0) — a version bump silently retires
  every old namespace, and a CPU-filled cache never hands a chip process its
  δ-model or observation log.

Known limit: *source edits* to schedule/engine construction code are not
content-hashed (package version strings don't change in a dev checkout, and
``PYTHONPATH=src`` runs pin the fallback version), so after changing how
schedules or rounds are *built*, bump :data:`CACHE_FORMAT` to retire every
persisted entry — that is what the constant is for.

Anything not captured by the namespace (δ, backend, frontier, mesh width,
argument shapes) is keyed per entry inside the namespace by
:mod:`repro.persist.store`.
"""

from __future__ import annotations

import hashlib

import jax
import numpy as np

__all__ = [
    "CACHE_FORMAT",
    "env_fingerprint",
    "graph_fingerprint",
    "plan_shard_fingerprint",
    "problem_fingerprint",
    "row_update_digest",
    "solver_namespace",
    "stripe_fingerprint",
]

# Bump to retire every existing cache entry (layout or semantics change).
# 2: FrontierPlan src_loc/rows_loc went shard-major (D, S, P_loc, ·).
# 3: the environment part names the device (platform, device_kind).
# 4: stripes and schedules carry row_last; rounds reduce by sorted scan.
CACHE_FORMAT = 4

try:  # installed package
    import importlib.metadata

    _REPRO_VERSION = importlib.metadata.version("repro")
except Exception:  # pragma: no cover - PYTHONPATH runs carry no dist metadata
    _REPRO_VERSION = "0.1.0"


def env_fingerprint(device=None) -> str:
    """The toolchain and device part of every namespace key (mismatch ⇒ cold).

    ``device`` defaults to ``jax.devices()[0]``: the device the solver's
    executables, probes and timings belong to.
    """
    device = jax.devices()[0] if device is None else device
    return (
        f"format{CACHE_FORMAT}-repro{_REPRO_VERSION}"
        f"-jax{jax.__version__}-numpy{np.__version__}"
        f"-{device.platform}-{device.device_kind}"
    )


def _digest(*parts: bytes) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p)
        h.update(b"\x00")  # unambiguous part boundaries
    return h.hexdigest()


def graph_fingerprint(graph) -> str:
    """Content hash of a :class:`~repro.graphs.formats.CSRGraph` (not its name)."""
    return _digest(
        str(graph.n).encode(),
        str(graph.indptr.dtype).encode(),
        np.ascontiguousarray(graph.indptr).tobytes(),
        str(graph.indices.dtype).encode(),
        np.ascontiguousarray(graph.indices).tobytes(),
        str(graph.values.dtype).encode(),
        np.ascontiguousarray(graph.values).tobytes(),
    )


def stripe_fingerprint(graph, lo: int, hi: int, S: int, delta: int, pad_val) -> str:
    """Content key of one worker stripe — the unit of evolve-aware reuse.

    Hashes exactly what :func:`repro.graphs.formats.build_worker_stripe`
    reads: the block's *relative* indptr slice plus its in-edge sources and
    values, the global ``n`` (source ids and the dump row reference it), the
    shape knobs ``(S, delta)``, the pad value/dtype, and the environment.
    Two graphs that differ only outside ``[lo, hi)`` produce the same digest
    for this block, which is what lets a mutated graph's schedule reuse every
    untouched stripe from the shared store.
    """
    indptr = np.asarray(graph.indptr)
    e0, e1 = int(indptr[lo]), int(indptr[hi])
    rel_ptr = indptr[lo : hi + 1] - e0
    return _digest(
        env_fingerprint().encode(),
        str(int(graph.n)).encode(),
        str(int(lo)).encode(),
        str(int(hi)).encode(),
        str(int(S)).encode(),
        str(int(delta)).encode(),
        repr(pad_val).encode(),
        str(graph.values.dtype).encode(),
        np.ascontiguousarray(rel_ptr).tobytes(),
        np.ascontiguousarray(graph.indices[e0:e1]).tobytes(),
        np.ascontiguousarray(graph.values[e0:e1]).tobytes(),
    )


def plan_shard_fingerprint(sched, vb_lo: int, vb_hi: int, w0: int, w1: int) -> str:
    """Content key of one frontier-plan shard piece (workers ``[w0, w1)``).

    Hashes what :func:`repro.dist.engine_sharded.build_plan_shard` reads: the
    shard's slices of the schedule's ``src``/``dst_local``/``rows`` arrays,
    its owned vertex interval, and ``(n, delta)``.  The shard-local index
    arrays (halo, src_loc, rows_loc) depend on nothing else, so a mutation
    that leaves these workers' stripes byte-identical reuses the piece.
    """
    return _digest(
        env_fingerprint().encode(),
        str(int(sched.n)).encode(),
        str(int(sched.delta)).encode(),
        str(int(vb_lo)).encode(),
        str(int(vb_hi)).encode(),
        np.ascontiguousarray(sched.worker_block("src", w0, w1)).tobytes(),
        np.ascontiguousarray(sched.worker_block("dst_local", w0, w1)).tobytes(),
        np.ascontiguousarray(sched.worker_block("rows", w0, w1)).tobytes(),
    )


def row_update_digest(row_update_q, semiring, q_template, feature_dim: int = 1) -> str:
    """Digest of the row update's traced jaxpr **plus closure constants**.

    ``row_update_q`` is the normalized 4-arg form
    ``(old, reduced, rows, q) -> new``.  Tracing with tiny abstract row blocks
    captures the update's computation graph and hoists its closure constants
    (Jacobi's ``b/diag`` table, PageRank's teleport scalar) into ``consts`` —
    both are hashed, so problems that differ only in baked-in data get
    distinct namespaces.  Untraceable updates degrade to a sentinel (their
    problems then only share entries with themselves via name/tol/semiring).

    ``feature_dim > 1`` traces with a trailing feature axis — matrix-frontier
    updates (row-normalizing label propagation, per-column RWR) see the rank
    they will run at; ``feature_dim == 1`` keeps the historical trace shapes,
    so every pre-existing vector digest is unchanged.
    """
    sds = jax.ShapeDtypeStruct
    dt = np.dtype(semiring.dtype)
    feat = (int(feature_dim),) if feature_dim > 1 else ()
    args = (
        sds((2, 3) + feat, dt),
        sds((2, 3) + feat, dt),
        sds((2, 3), np.int32),
        jax.tree_util.tree_map(
            lambda a: sds(np.shape(a), np.asarray(a).dtype), q_template
        ),
    )
    try:
        closed = jax.make_jaxpr(row_update_q)(*args)
    except Exception:
        return "untraceable"
    h = hashlib.sha256(str(closed.jaxpr).encode())
    for c in closed.consts:
        arr = np.asarray(c)
        h.update(str(arr.dtype).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def problem_fingerprint(problem, row_update_q, semiring, q_template) -> str:
    """Fingerprint of a :class:`~repro.solve.problem.Problem` instance.

    Matrix problems (``feature_dim > 1``) contribute an extra ``F<dim>`` part
    and trace the row update at matrix rank; vector problems hash exactly the
    historical parts, so existing on-disk namespaces stay warm.
    """
    feature_dim = int(getattr(problem, "feature_dim", 1))
    parts = [
        problem.name.encode(),
        repr(float(problem.tol)).encode(),
        str(int(problem.max_rounds)).encode(),
        str(np.dtype(semiring.dtype)).encode(),
        repr(semiring.zero).encode(),
        str(bool(problem.takes_query)).encode(),
        row_update_digest(
            row_update_q, semiring, q_template, feature_dim=feature_dim
        ).encode(),
    ]
    if feature_dim > 1:
        parts.append(f"F{feature_dim}".encode())
    return _digest(*parts)


def solver_namespace(
    graph,
    problem,
    row_update_q,
    q_template,
    n_workers: int,
    partition_method: str,
    min_chunk: int,
    tol: float,
    max_rounds: int,
) -> str:
    """The namespace key one Solver's persisted entries live under.

    ``tol``/``max_rounds`` are the solver's *effective* values (constructor
    overrides applied) — two solvers on one problem with different
    convergence regimes must not share a δ-model or observation log.
    """
    return _digest(
        env_fingerprint().encode(),
        graph_fingerprint(graph).encode(),
        problem_fingerprint(
            problem, row_update_q, problem.semiring, q_template
        ).encode(),
        str(int(n_workers)).encode(),
        partition_method.encode(),
        str(int(min_chunk)).encode(),
        repr(float(tol)).encode(),
        str(int(max_rounds)).encode(),
    )
