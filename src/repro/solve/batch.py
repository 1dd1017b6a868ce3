"""Batched multi-query solving: Q queries, one schedule, one lowering.

``solve_batch`` vmaps the solver's round function over a batch of initial
states (and, for query-parameterized problems, a batch of query params) and
runs one fused ``lax.while_loop`` until *every* query converges.  This is the
serving-scale scenario: multi-source SSSP or personalized PageRank answered
as a single device program against a warm schedule — no per-query stripe
builds, no per-query retraces, one commit collective per flush shared by the
whole batch.

``backend="sharded"`` vmaps the ``shard_map`` round instead of the
single-device one, so the whole batch spans the worker mesh in one lowering —
with ``frontier="halo"`` each commit moves only boundary entries while all Q
queries ride the same collectives.

Converged queries keep iterating (at their fixed point for idempotent
semirings like min-plus) until the stragglers finish; ``rounds_per_query``
records when each one first converged.  ``compact_every=k`` bounds that
straggler tax: every ``k`` rounds the unconverged subset is gathered on the
host and the loop continues on the smaller batch (one extra compile per
distinct active size); ``compact_every=None`` preserves the single fused
call bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import round_fn_pallas_q, round_fn_q_dyn, schedule_args
from repro.ft.inject import fire

__all__ = ["BatchResult", "BatchStepper", "RetiredQuery", "solve_batch"]


@dataclasses.dataclass
class BatchResult:
    """Result of one batched solve (Q queries sharing one schedule)."""

    x: np.ndarray  # (Q, n) or (Q, n, F) per-query converged states
    rounds: int  # rounds executed by the shared loop (= max over queries)
    rounds_per_query: np.ndarray  # (Q,) round of first convergence (0 = never)
    converged: np.ndarray  # (Q,) bool
    residuals: np.ndarray  # (Q,) final per-query residuals
    flushes: int  # schedule commits executed (shared by the batch)
    flush_bytes: int  # bytes published across the whole batch
    delta: int
    P: int
    Q: int
    compile_time_s: float = 0.0  # 0 on a warm cache
    total_time_s: float = 0.0
    compactions: int = 0  # straggler-compaction shrinks performed


def _batched_round(solver, sched, backend: str, frontier: str, feature_dims: int = 0):
    """Build ``(X_ext, qb, *args) -> X_ext`` running one round for all Q queries.

    Returns ``(rnd, args)``: ``args`` are the schedule (and halo-plan) arrays,
    which the compiled loop takes as arguments instead of closing over them,
    so XLA never embeds gigabytes of stripes in the executable.  The pallas
    kernel bakes its schedule into the grid and takes none.

    ``feature_dims`` is 0 for vector frontiers (``X_ext`` is ``(Q, n+1)``)
    and 1 for matrix frontiers (``(Q, n+1, F)``); the sharded builders need
    it to size their per-shard partition specs.
    """
    sr = solver.problem.semiring
    if backend == "pallas" and frontier == "halo":
        # vmapping a shard_map-of-pallas program is not supported; the
        # sharded backend runs the same halo exchange (in XLA) batched.
        raise ValueError(
            "batched halo solves use backend='sharded', frontier='halo' "
            "(backend='pallas' fuses per-shard kernels and cannot be vmapped)"
        )
    if backend == "jit":
        rnd = round_fn_q_dyn(sched, sr, solver._row_update_q)
        sargs = schedule_args(sched)
        return jax.vmap(rnd, in_axes=(0, 0) + (None,) * len(sargs)), sargs
    if backend == "pallas":
        rnd = round_fn_pallas_q(sched, sr, solver._row_update_q)
        return jax.vmap(rnd, in_axes=(0, 0)), ()
    if backend != "sharded":
        raise ValueError(
            f"batch backend must be 'jit', 'pallas', or 'sharded': {backend!r}"
        )
    mesh = solver._default_mesh()
    if frontier == "replicated":
        from repro.dist.engine_sharded import sharded_round_fn_q

        base = sharded_round_fn_q(
            sched, sr, solver._row_update_q, mesh, axis=solver.mesh_axis,
            feature_dims=feature_dims,
        )
        sargs = schedule_args(sched)
        vm = jax.vmap(base, in_axes=(0,) + (None,) * len(sargs) + (0,))
        return (lambda X, qb, *args: vm(X, *args, qb)), sargs
    from repro.dist.engine_sharded import frontier_plan_args, frontier_round_ext_fn

    plan = solver.frontier_plan(sched)
    ext = frontier_round_ext_fn(
        sched, plan, sr, solver._row_update_q, mesh, axis=solver.mesh_axis,
        feature_dims=feature_dims,
    )
    args = frontier_plan_args(sched, plan)
    return jax.vmap(ext, in_axes=(0, 0) + (None,) * len(args)), args


def _make_batch_solve_fn(rnd, residual_fn):
    """``(X_ext, qb, tol, max_rounds, *args) -> carry`` over a batched round fn."""
    res_fn = jax.vmap(residual_fn, in_axes=(0, 0))

    def solve_loop(X_ext, qb, tol, max_rounds, *args):
        def cond(carry):
            _, _, rounds, converged, _ = carry
            return jnp.logical_and(rounds < max_rounds, ~jnp.all(converged))

        def body(carry):
            X, _, rounds, converged, rpq = carry
            X_new = rnd(X, qb, *args)
            res = res_fn(X[:, :-1], X_new[:, :-1]).astype(jnp.float32)
            # stamp only at first convergence; never-converged queries keep 0
            just_converged = jnp.logical_and(~converged, res <= tol)
            rpq = jnp.where(just_converged, rounds + 1, rpq)
            return X_new, res, rounds + 1, converged | (res <= tol), rpq

        Q = X_ext.shape[0]
        init = (
            X_ext,
            jnp.full((Q,), np.inf, jnp.float32),
            jnp.asarray(0, jnp.int32),
            jnp.zeros((Q,), bool),
            jnp.zeros((Q,), jnp.int32),
        )
        return jax.lax.while_loop(cond, body, init)

    return solve_loop


def _make_open_batch_solve_fn(rnd, residual_fn):
    """``(X_ext, qb, conv0, tol, max_rounds, *args) -> carry`` for an *open* batch.

    Two deltas from :func:`_make_batch_solve_fn`, both load-bearing for
    continuous batching:

    * rows may start already-converged (``conv0``) — that is how empty queue
      slots ride along in a fixed-shape compiled loop without blocking the
      convergence test;
    * a row **freezes at first convergence**: once its residual crosses tol
      its state stops updating, so the value a slot retires with is exactly
      the value a fresh ``solve_batch`` of that query alone would return —
      bit-identical, regardless of how many extra rounds its batchmates need.
    """
    res_fn = jax.vmap(residual_fn, in_axes=(0, 0))

    def solve_loop(X_ext, qb, conv0, tol, max_rounds, *args):
        def cond(carry):
            _, _, rounds, converged, _ = carry
            return jnp.logical_and(rounds < max_rounds, ~jnp.all(converged))

        def body(carry):
            X, res_prev, rounds, converged, rpq = carry
            X_new = rnd(X, qb, *args)
            res = res_fn(X[:, :-1], X_new[:, :-1]).astype(jnp.float32)
            just_converged = jnp.logical_and(~converged, res <= tol)
            rpq = jnp.where(just_converged, rounds + 1, rpq)
            conv_b = converged.reshape(converged.shape + (1,) * (X.ndim - 1))
            X_keep = jnp.where(conv_b, X, X_new)
            res_keep = jnp.where(converged, res_prev, res)
            return X_keep, res_keep, rounds + 1, converged | (res <= tol), rpq

        Q = X_ext.shape[0]
        init = (
            X_ext,
            jnp.full((Q,), np.inf, jnp.float32),
            jnp.asarray(0, jnp.int32),
            conv0,
            jnp.zeros((Q,), jnp.int32),
        )
        return jax.lax.while_loop(cond, body, init)

    return solve_loop


@dataclasses.dataclass
class RetiredQuery:
    """One slot retired from a :class:`BatchStepper` quantum."""

    tag: object  # caller's identifier, passed through admit()
    x: np.ndarray  # (n,) or (n, F) final state (frozen at first convergence)
    rounds: int  # rounds to first convergence (total, across quanta)
    converged: bool  # False = retired on the max_rounds budget
    residual: float


class BatchStepper:
    """A fixed-capacity *open* batch: admit mid-flight, retire converged.

    This is the continuous-batching primitive under
    :mod:`repro.launch.service`.  Where :func:`solve_batch` answers one
    closed set of queries, a stepper owns ``capacity`` slots of one compiled
    loop and interleaves three operations:

    * :meth:`admit` writes a query's initial state (and query params) into a
      free slot;
    * :meth:`run` executes one scheduling quantum — at most ``quantum``
      rounds of the fused loop over **all** slots (free slots ride along
      pre-converged, so the compiled shape never changes);
    * converged slots (and slots out of round budget) retire from
      :meth:`run` as :class:`RetiredQuery` rows, freeing their slots for
      the next admissions.

    Rows are row-independent under ``vmap`` and freeze at first convergence,
    so a retired result is bit-identical to a fresh ``solve_batch`` of that
    query alone — no matter when it slotted in or who shared the batch
    (asserted in ``tests/test_serve_scheduler.py``).

    The compiled loop is cached on the solver under
    ``("batch", "open", backend, frontier, δ, capacity)`` and persists to the
    store like every other executable, so a restarted service still serves
    its first quantum with zero retraces.
    """

    def __init__(
        self,
        solver,
        capacity: int,
        *,
        delta=None,
        backend: str | None = None,
        frontier: str | None = None,
        tol=None,
        max_rounds=None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        backend = backend or (
            solver.default_backend if solver.default_backend != "host" else "jit"
        )
        if backend == "host":  # host rounds are not vmappable; jit is the
            backend = "jit"  # same XLA round iterated on-device
        self.solver = solver
        self.backend = backend
        self.frontier = solver.resolve_frontier(frontier, backend)
        self.sched = solver.schedule(delta)
        self.capacity = capacity
        self.tol = solver.tol if tol is None else tol
        self.max_rounds = solver.max_rounds if max_rounds is None else max_rounds
        sr = solver.problem.semiring
        self._sr = sr
        n = solver.graph.n
        # Matrix problems (feature_dim > 1) give every slot a (n+1, F) state;
        # scalar problems keep the historical (n+1,) layout bit-for-bit.
        F = getattr(solver.problem, "feature_dim", 1)
        self._feat = (F,) if F > 1 else ()
        self._X = np.full((capacity, n + 1) + self._feat, sr.zero, dtype=sr.dtype)
        if solver.problem.takes_query:
            self._qb = None  # built from the first admitted row's structure
        else:
            self._qb = np.zeros((capacity,), np.int32)
        self._occupied = np.zeros(capacity, bool)
        self._tags: list = [None] * capacity
        self._rounds_in = np.zeros(capacity, np.int64)
        self.flushes = 0
        self.flush_bytes = 0
        self.rounds_executed = 0  # cumulative, across all quanta
        self.quanta = 0
        key_tail: tuple = ()
        if backend == "sharded":
            from repro.dist.compat import mesh_axis_sizes

            key_tail = (mesh_axis_sizes(solver._default_mesh())[solver.mesh_axis],)
        fk: tuple = ("F", F) if self._feat else ()
        self._key = (
            "batch",
            "open",
            backend,
            self.frontier,
            self.sched.delta,
            capacity,
        ) + key_tail + fk
        self._portable = key_tail in ((), (1,))

    # -------------------------------------------------------------- slots #
    @property
    def occupancy(self) -> int:
        return int(self._occupied.sum())

    @property
    def free_slots(self) -> int:
        return self.capacity - self.occupancy

    def admit(self, x0, q=None, tag=None) -> int:
        """Write one query into a free slot; returns the slot index."""
        free = np.nonzero(~self._occupied)[0]
        if free.size == 0:
            raise ValueError("no free slots (retire via run() first)")
        slot = int(free[0])
        x0 = np.asarray(x0, dtype=self._sr.dtype)
        n = self.solver.graph.n
        want = (n,) + self._feat
        if x0.shape != want:
            raise ValueError(f"x0 must have shape {want}, got {x0.shape}")
        self._X[slot, :n] = x0
        self._X[slot, n] = self._sr.zero
        if self.solver.problem.takes_query:
            if q is None:
                raise ValueError(
                    f"problem {self.solver.problem.name!r} needs a per-row q="
                )
            if self._qb is None:
                self._qb = jax.tree_util.tree_map(
                    lambda leaf: np.zeros(
                        (self.capacity,) + np.shape(leaf), np.asarray(leaf).dtype
                    ),
                    q,
                )
            leaves_b, leaves_q = (
                jax.tree_util.tree_leaves(self._qb),
                jax.tree_util.tree_leaves(q),
            )
            for dst, row in zip(leaves_b, leaves_q):
                dst[slot] = row
        elif q is not None:
            raise ValueError(f"problem {self.solver.problem.name!r} takes no query")
        self._occupied[slot] = True
        self._tags[slot] = tag
        self._rounds_in[slot] = 0
        return slot

    # ---------------------------------------------------------------- run #
    def _compiled_loop(self, X_ext, qb, conv0, tol_a, rounds_a):
        """The compiled open-batch loop and the arrays it takes after ``rounds``."""
        rnd, args = _batched_round(
            self.solver, self.sched, self.backend, self.frontier,
            feature_dims=len(self._feat),
        )
        fn = self.solver.compile_cached(
            self._key,
            _make_open_batch_solve_fn(rnd, self.solver.problem.residual),
            X_ext,
            qb,
            conv0,
            tol_a,
            rounds_a,
            *args,
            portable=self._portable,
        )
        return fn, args

    def evict_all(self) -> list:
        """Clear every occupied slot and return their tags (fault recovery).

        After a faulted quantum the batch state is suspect; the scheduler
        evicts the riders (requeueing them for retry elsewhere) and drops the
        lane.  The stepper itself is left empty but reusable.
        """
        tags = [self._tags[slot] for slot in np.nonzero(self._occupied)[0]]
        self._occupied[:] = False
        self._tags = [None] * self.capacity
        return tags

    def run(self, quantum: int) -> list[RetiredQuery]:
        """One scheduling quantum: at most ``quantum`` rounds, then retire.

        Returns the slots that finished this quantum (first convergence, or
        the ``max_rounds`` budget exhausted — at quantum granularity).  No-op
        on an empty batch.
        """
        if quantum < 1:
            raise ValueError(f"quantum must be >= 1, got {quantum}")
        occ = self._occupied
        if not occ.any():
            return []
        # chaos hook before any state mutates: a kernel fault here leaves the
        # stepper untouched, so the scheduler can evict + retry its riders
        fire("kernel.dispatch", backend=self.backend, frontier=self.frontier)
        sr = self._sr
        t0 = time.perf_counter()
        X_ext = jnp.asarray(self._X)
        qb = jax.tree_util.tree_map(jnp.asarray, self._qb)
        conv0 = jnp.asarray(~occ)
        tol_a = jnp.asarray(self.tol, jnp.float32)
        rounds_a = jnp.asarray(quantum, jnp.int32)
        fn, args = self._compiled_loop(X_ext, qb, conv0, tol_a, rounds_a)
        X_new, res, r, conv, rpq = fn(X_ext, qb, conv0, tol_a, rounds_a, *args)
        X_new.block_until_ready()
        r = int(r)
        # np.array (copy), not np.asarray: device buffers are read-only and
        # the next admit() writes into this array in place
        self._X = np.array(X_new)
        conv_np, res_np, rpq_np = np.asarray(conv), np.asarray(res), np.asarray(rpq)
        before = self._rounds_in.copy()
        self._rounds_in[occ] += r
        self.rounds_executed += r
        self.quanta += 1
        self.flushes += r * self.sched.S
        F = int(np.prod(self._feat, dtype=np.int64)) if self._feat else 1
        bytes_per = np.dtype(sr.dtype).itemsize * F
        per_round = self.sched.S * self.sched.P * self.sched.delta * bytes_per
        self.flush_bytes += r * per_round * self.capacity
        n = self.solver.graph.n
        retired: list[RetiredQuery] = []
        for slot in np.nonzero(occ)[0]:
            done = bool(conv_np[slot])
            if not done and self._rounds_in[slot] < self.max_rounds:
                continue
            if done:
                rounds = int(before[slot] + rpq_np[slot])
            else:
                rounds = int(self._rounds_in[slot])
            retired.append(
                RetiredQuery(
                    tag=self._tags[slot],
                    x=self._X[slot, :n].copy(),
                    rounds=rounds,
                    converged=done,
                    residual=float(res_np[slot]),
                )
            )
            self._occupied[slot] = False
            self._tags[slot] = None
        self.solver.stats["solves"] += len(retired)
        finished = [q.rounds for q in retired if q.converged]
        if finished:
            # one (δ, rounds) datapoint per quantum-with-retirees, max over
            # the finishers — same conservative convention as solve_batch
            self.solver._record_observation(
                self.sched.delta,
                max(finished),
                time.perf_counter() - t0,
                self.backend,
                kind="batch",
            )
        return retired


def solve_batch(
    solver,
    x0_batch,
    *,
    q=None,
    delta=None,
    backend: str | None = None,
    frontier: str | None = None,
    tol=None,
    max_rounds=None,
    compact_every: int | None = None,
) -> BatchResult:
    """Solve Q queries of ``solver.problem`` in one compiled device loop.

    * ``x0_batch``      — (Q, n) initial states (e.g. :func:`multi_source_x0`),
      or (Q, n, F) for matrix-frontier problems (e.g. batched RWR embeddings).
    * ``q``             — for query problems, a pytree whose leaves have a
      leading Q axis (e.g. :func:`ppr_teleport`); must be ``None`` otherwise.
    * ``backend``       — ``"jit"`` (default: vmapped single-device round),
      ``"pallas"`` (vmapped fused one-kernel round — the whole batch shares
      the VMEM-resident commit pipeline), or ``"sharded"`` (vmapped
      ``shard_map`` round spanning the worker mesh); ``frontier`` picks
      replicated vs halo for the sharded round.
    * ``compact_every`` — shrink the active batch to the unconverged subset
      every this many rounds (straggler-aware batching); ``None`` runs one
      fused loop until the slowest query converges, bit-for-bit as before.

    ``solve_batch`` with ``Q == 1`` is bit-identical to the unbatched
    ``backend="jit"`` path: same round function, same residual rule, same
    stopping round.  The compiled loop is cached on the solver keyed by
    ``(backend, frontier, δ, Q)``; repeated batches of the same shape never
    retrace.
    """
    problem = solver.problem
    sr = problem.semiring
    backend = backend or (
        solver.default_backend if solver.default_backend != "host" else "jit"
    )
    frontier = solver.resolve_frontier(frontier, backend)
    sched = solver.schedule(delta)
    tol = solver.tol if tol is None else tol
    max_rounds = solver.max_rounds if max_rounds is None else max_rounds
    if compact_every is not None and compact_every < 1:
        raise ValueError(f"compact_every must be >= 1, got {compact_every}")

    X = jnp.asarray(x0_batch, dtype=sr.dtype)
    if X.ndim not in (2, 3) or X.shape[1] != solver.graph.n:
        raise ValueError(
            f"x0_batch must be (Q, {solver.graph.n}) or "
            f"(Q, {solver.graph.n}, F), got {X.shape}"
        )
    Q = X.shape[0]
    feat = X.shape[2:]
    F = int(np.prod(feat, dtype=np.int64)) if feat else 1
    fk: tuple = ("F", F) if feat else ()
    X_ext = jnp.concatenate(
        [X, jnp.full((Q, 1) + feat, sr.zero, dtype=sr.dtype)], axis=1
    )

    if problem.takes_query:
        if q is None:
            raise ValueError(f"problem {problem.name!r} needs a batched q=")
        qb = jax.tree_util.tree_map(jnp.asarray, q)
        lead = jax.tree_util.tree_leaves(qb)[0].shape[0]
        if lead != Q:
            raise ValueError(f"q leading axis {lead} != Q {Q}")
    else:
        if q is not None:
            raise ValueError(f"problem {problem.name!r} takes no query")
        qb = jnp.zeros((Q,), jnp.int32)

    tol_a = jnp.asarray(tol, jnp.float32)
    bytes_per = np.dtype(sr.dtype).itemsize * F

    # Sharded loops are additionally keyed by mesh width: a persisted
    # executable exported by a 1-device process must never satisfy an
    # 8-device one (single-device exports are the only ones persisted).
    key_tail: tuple = ()
    if backend == "sharded":
        from repro.dist.compat import mesh_axis_sizes

        key_tail = (mesh_axis_sizes(solver._default_mesh())[solver.mesh_axis],)

    rnd, args = _batched_round(solver, sched, backend, frontier, len(feat))

    def compiled_loop(X_cur, qb_cur):
        """The fused loop for the current active size (cached per size)."""
        return solver.compile_cached(
            ("batch", backend, frontier, sched.delta, X_cur.shape[0])
            + key_tail
            + fk,
            _make_batch_solve_fn(rnd, problem.residual),
            X_cur,
            qb_cur,
            tol_a,
            jnp.asarray(max_rounds, jnp.int32),
            *args,
            # a >1-device shard_map export pins its device assignment and
            # could never load — skip the store instead of exporting to waste
            portable=key_tail in ((), (1,)),
        )

    solver.stats["solves"] += 1
    x_out = np.empty((Q, solver.graph.n) + feat, dtype=sr.dtype)
    rpq_all = np.zeros(Q, np.int32)
    conv_all = np.zeros(Q, bool)
    res_all = np.full(Q, np.inf, np.float32)
    active = np.arange(Q)
    rounds_done = 0
    flushes = 0
    flush_bytes = 0
    compile_time_s = 0.0
    compactions = 0
    t0 = time.perf_counter()
    while active.size:
        chunk = max_rounds - rounds_done
        if compact_every is not None:
            chunk = min(chunk, compact_every)
        fn = compiled_loop(X_ext, qb)
        compile_time_s += solver._last_compile_s
        X_new, res, r, conv, rpq = fn(
            X_ext, qb, tol_a, jnp.asarray(chunk, jnp.int32), *args
        )
        X_new.block_until_ready()
        r = int(r)
        rounds_done += r
        flushes += r * sched.S
        flush_bytes += r * sched.S * sched.P * sched.delta * bytes_per * active.size
        conv_np = np.asarray(conv)
        rpq_np = np.asarray(rpq)
        rpq_all[active] = np.where(rpq_np > 0, rounds_done - r + rpq_np, 0)
        conv_all[active] = conv_np
        res_all[active] = np.asarray(res)
        if conv_np.all() or rounds_done >= max_rounds:
            x_out[active] = np.asarray(X_new[:, :-1])
            break
        # Straggler compaction: keep only converged rows' states on the host
        # (their final answers) and continue on the unconverged subset.
        if conv_np.any():
            done = jnp.asarray(np.nonzero(conv_np)[0])
            x_out[active[conv_np]] = np.asarray(X_new[done, :-1])
            keep = jnp.asarray(np.nonzero(~conv_np)[0])
            active = active[~conv_np]
            X_new = X_new[keep]
            qb = jax.tree_util.tree_map(lambda a: a[keep], qb)
            compactions += 1
        X_ext = X_new
    total = time.perf_counter() - t0

    # Batch rounds are max-over-queries (tagged "batch" so the refit can tell)
    # — routed through the solver so served traffic advances reprobe_every's
    # counter: in a serving process, batches ARE the production observations.
    solver._record_observation(
        sched.delta, rounds_done, total, backend, kind="batch"
    )

    return BatchResult(
        x=x_out,
        rounds=rounds_done,
        rounds_per_query=rpq_all,
        converged=conv_all,
        residuals=res_all,
        flushes=flushes,
        flush_bytes=flush_bytes,
        delta=sched.delta,
        P=sched.P,
        Q=Q,
        compile_time_s=compile_time_s,
        total_time_s=total,
        compactions=compactions,
    )
