"""The delayed-asynchronous iterative engine (the paper's contribution).

One *round* processes every vertex once, in ``S`` **commit steps**.  Commit
step ``s`` computes, for every worker in parallel, the pull-update of chunk
``s`` (δ rows) of that worker's block reading the *current committed* frontier,
then publishes all workers' chunks to the frontier simultaneously.  This is a
deterministic block Gauss–Seidel schedule with commit period δ — the TPU-native
semantics of the paper's thread-local buffer flush (DESIGN.md §2, §5):

* ``S == 1``   (δ = block size)  → exact Jacobi          = paper's *synchronous*
* ``S == B/δ_min`` (finest δ)    → finest block GS       = paper's *asynchronous*
* in between                     → *delayed asynchronous* (the hybrid)

The engine is mode-free: the mode IS the schedule's δ.  Counters for flushes
and flush bytes (the TPU analogue of cache-line invalidation traffic) are
reported on every run.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import Semiring
from repro.ft.inject import fire
from repro.graphs.formats import CSRGraph, StripeSchedule, build_stripe_schedule
from repro.graphs.partition import balanced_blocks

__all__ = [
    "EngineResult",
    "DeviceSchedule",
    "make_schedule",
    "round_fn",
    "round_fn_q",
    "round_fn_pallas",
    "round_fn_pallas_q",
    "make_solve_fn",
    "make_solve_fn_q",
    "make_solve_fn_q_dyn",
    "round_fn_q_dyn",
    "schedule_args",
    "host_loop",
    "execute_solve_fn",
    "changed_rows",
    "run_host",
    "run_jit",
    "extend_frontier",
    "MIN_CHUNK",
]

# Finest vectorizable commit granularity (DESIGN.md §2): the TPU analogue of
# the paper's one-cache-line δ=16.  One VPU lane row = 128 elements.
MIN_CHUNK = 128


def extend_frontier(x0, semiring: Semiring):
    """Append the padding-dump slot: ``(n,)+feat → (n+1,)+feat``.

    The frontier may be a vector ``(n,)`` or a matrix ``(n, F)``; the dump
    row (index ``n``, where padded edges and padded δ-rows land) is filled
    with the ⊕-identity either way.  One authority for the extended-frontier
    layout shared by every runner, the Solver, and the batch path.
    """
    x0 = jnp.asarray(x0, dtype=semiring.dtype)
    pad = jnp.full((1,) + x0.shape[1:], semiring.zero, dtype=semiring.dtype)
    return jnp.concatenate([x0, pad])


@dataclasses.dataclass(frozen=True)
class DeviceSchedule:
    """StripeSchedule moved to device (jax arrays) + metadata.

    Every constructor takes ``put``, which places each ``(S, P, ·)`` array:
    ``jnp.asarray`` (whole, on the default device) unless a caller passes a
    ``device_put`` onto a sharding, as the Solver's sharded backend does so
    that each chip receives only its own workers' stripes from the host.
    """

    n: int
    P: int
    delta: int
    S: int
    M: int
    src: jnp.ndarray  # (S, P, M) int32
    val: jnp.ndarray  # (S, P, M)
    dst_local: jnp.ndarray  # (S, P, M) int32
    rows: jnp.ndarray  # (S, P, delta) int32
    edges: int
    padding_overhead: float
    block_bounds: np.ndarray | None = None  # (P + 1,) int64 host-side bounds

    @property
    def n_slots(self) -> int:
        return self.n + 1

    def worker_block(self, name: str, w0: int, w1: int) -> np.ndarray:
        """Host copy of workers ``[w0, w1)`` of the ``(S, P, ·)`` array ``name``.

        Where one device holds exactly that block (the sharded backend's
        layout), only that shard leaves the device; otherwise the array comes
        to the host shard by shard and is sliced there.  Never slices on the
        device, where a sharded array would be gathered whole onto each chip.
        """
        arr = getattr(self, name)
        for shard in getattr(arr, "addressable_shards", ()):
            cols = shard.index[1]
            if (cols.start or 0, self.P if cols.stop is None else cols.stop) == (
                w0,
                w1,
            ):
                return np.asarray(shard.data)
        return np.asarray(arr)[:, w0:w1]

    # ------------------------------------------------------------------ #
    # persistence (repro.persist stores schedules as plain npz archives)
    # ------------------------------------------------------------------ #
    def to_host_arrays(self) -> dict:
        """Flat ``{name: ndarray}`` dict round-trippable through ``np.savez``."""
        return {
            "n": np.int64(self.n),
            "P": np.int64(self.P),
            "delta": np.int64(self.delta),
            "S": np.int64(self.S),
            "M": np.int64(self.M),
            "src": np.asarray(self.src),
            "val": np.asarray(self.val),
            "dst_local": np.asarray(self.dst_local),
            "rows": np.asarray(self.rows),
            "edges": np.int64(self.edges),
            "padding_overhead": np.float64(self.padding_overhead),
            "block_bounds": np.asarray(
                self.block_bounds if self.block_bounds is not None else []
            ),
        }

    @classmethod
    def from_stripes(cls, host: StripeSchedule, put=jnp.asarray) -> "DeviceSchedule":
        """Move a host :class:`StripeSchedule` to the device through ``put``."""
        return cls(
            n=host.n,
            P=host.P,
            delta=host.delta,
            S=host.S,
            M=host.M,
            src=put(host.src),
            val=put(host.val),
            dst_local=put(host.dst_local),
            rows=put(host.rows),
            edges=host.edges,
            padding_overhead=host.padding_overhead,
            block_bounds=np.asarray(host.block_bounds),
        )

    @classmethod
    def from_host_arrays(cls, arrays, put=jnp.asarray) -> "DeviceSchedule":
        """Rebuild from :meth:`to_host_arrays` output (shape-validated)."""
        n, P = int(arrays["n"]), int(arrays["P"])
        delta, S, M = int(arrays["delta"]), int(arrays["S"]), int(arrays["M"])
        src = np.asarray(arrays["src"])
        val = np.asarray(arrays["val"])
        dst_local = np.asarray(arrays["dst_local"])
        rows = np.asarray(arrays["rows"])
        bb = np.asarray(arrays["block_bounds"])
        if (
            src.shape != (S, P, M)
            or val.shape != (S, P, M)
            or dst_local.shape != (S, P, M)
            or rows.shape != (S, P, delta)
        ):
            raise ValueError("schedule arrays inconsistent with (S, P, M, delta)")
        return cls(
            n=n,
            P=P,
            delta=delta,
            S=S,
            M=M,
            src=put(src),
            val=put(val),
            dst_local=put(dst_local),
            rows=put(rows),
            edges=int(arrays["edges"]),
            padding_overhead=float(arrays["padding_overhead"]),
            block_bounds=bb.astype(np.int64) if bb.size else None,
        )


def make_schedule(
    graph: CSRGraph,
    P: int,
    delta: int | None,
    semiring: Semiring,
    mode: str = "delayed",
    min_chunk: int = MIN_CHUNK,
    bounds: np.ndarray | None = None,
    put=jnp.asarray,
) -> DeviceSchedule:
    """Build the device schedule for ``mode`` ∈ {sync, async, delayed}.

    * ``sync``    → δ = max block size (one commit per round).
    * ``async``   → δ = ``min_chunk`` (finest vectorizable commit).
    * ``delayed`` → δ as given (the paper's tunable).

    ``bounds`` overrides the default :func:`balanced_blocks` partition (any
    contiguous (P + 1,) bounds, e.g. from
    :func:`repro.graphs.partition.make_partition`).  ``put`` places the
    arrays (see :class:`DeviceSchedule`).
    """
    if bounds is None:
        bounds = balanced_blocks(graph, P)
    else:
        bounds = np.asarray(bounds, dtype=np.int64)
        if bounds.shape != (P + 1,):
            raise ValueError(f"bounds must have shape ({P + 1},), got {bounds.shape}")
        if bounds[0] != 0 or bounds[-1] != graph.n or (np.diff(bounds) < 0).any():
            raise ValueError("bounds must cover [0, n] with monotone cuts")
    B = int(np.diff(bounds).max())
    if mode == "sync":
        delta_eff = B
    elif mode == "async":
        delta_eff = min(min_chunk, B)
    elif mode == "delayed":
        assert delta is not None, "delayed mode needs δ"
        delta_eff = int(min(max(delta, 1), B))
    else:
        raise ValueError(f"unknown mode {mode!r}")
    host = build_stripe_schedule(graph, bounds, delta_eff, semiring.pad_edge_val)
    return DeviceSchedule.from_stripes(host, put)


def _commit_step(
    s, x_ext, sched: DeviceSchedule, semiring: Semiring, row_update, q=None
):
    """One commit step: chunk-SpMV for all workers + publish.

    Shape-generic over the frontier's trailing feature axes: ``x_ext`` may be
    ``(n+1,)`` (the classic vector engine) or ``(n+1, F)`` (matrix frontiers).
    For the vector case every reshape below is the identity, so the emitted
    computation — and therefore the result — is bit-identical to the
    historical vector-only commit step.
    """
    P, delta = sched.P, sched.delta
    feat = x_ext.shape[1:]  # () for vector state, (F,) for matrix state
    # Three named scopes, one per kind of work; they reach each op's
    # ``op_name`` metadata (and so a device trace) and emit no ops.  A fused
    # kernel is named by its root op, so each scope ends with the reshape that
    # hands its result on.
    with jax.named_scope("commit.gather"):
        src_s = jax.lax.dynamic_index_in_dim(sched.src, s, 0, keepdims=False)
        val_s = jax.lax.dynamic_index_in_dim(sched.val, s, 0, keepdims=False)
        dst_s = jax.lax.dynamic_index_in_dim(sched.dst_local, s, 0, keepdims=False)
        rows_s = jax.lax.dynamic_index_in_dim(sched.rows, s, 0, keepdims=False)
        gathered = x_ext[src_s]  # (P, M) + feat — reads the committed frontier
        # Edge values broadcast over the feature axis: one ⊗ weight per edge.
        val_b = val_s.reshape(val_s.shape + (1,) * len(feat))
        contrib = semiring.mul(gathered, val_b).reshape((-1,) + feat)  # (P·M,)+feat
    with jax.named_scope("commit.segment_reduce"):
        # Per-worker segment-⊕ into δ + 1 slots (last = padding dump).
        seg = dst_s + (jnp.arange(P, dtype=jnp.int32) * (delta + 1))[:, None]
        reduced = semiring.segment_reduce(
            contrib, seg.reshape(-1), P * (delta + 1)
        ).reshape((P, delta + 1) + feat)[:, :delta]
    with jax.named_scope("commit.publish"):
        old = x_ext[rows_s]  # (P, delta) + feat
        if q is None:
            new = row_update(old, reduced, rows_s)
        else:
            new = row_update(old, reduced, rows_s, q)
        # Publish: the flush.  Padding rows all point at the dump slot (index n).
        return x_ext.at[rows_s.reshape(-1)].set(
            new.reshape((-1,) + feat).astype(x_ext.dtype),
            mode="drop",
            unique_indices=False,
        )


def round_fn(sched: DeviceSchedule, semiring: Semiring, row_update) -> Callable:
    """Return jit-able ``x_ext -> x_ext`` running one full round (S commits)."""

    def body(x_ext):
        step = partial(
            _commit_step, sched=sched, semiring=semiring, row_update=row_update
        )
        return jax.lax.fori_loop(0, sched.S, step, x_ext)

    return body


def round_fn_q(sched: DeviceSchedule, semiring: Semiring, row_update) -> Callable:
    """Return jit-able ``(x_ext, q) -> x_ext`` for query-parameterized problems.

    ``q`` is a per-query pytree (e.g. a personalized-PageRank teleport vector)
    threaded to ``row_update(old, reduced, rows, q)``.  Keeping ``q`` a formal
    argument (rather than a closure constant) is what lets
    :func:`repro.solve.batch.solve_batch` vmap one round function over a batch
    of queries in a single lowering.
    """

    def body(x_ext, q):
        step = partial(
            _commit_step, sched=sched, semiring=semiring, row_update=row_update, q=q
        )
        return jax.lax.fori_loop(0, sched.S, step, x_ext)

    return body


def round_fn_pallas(
    sched: DeviceSchedule, semiring: Semiring, row_update, interpret: bool | None = None
) -> Callable:
    """``x_ext -> x_ext``: one round as a single fused Pallas kernel.

    Drop-in for :func:`round_fn` — same schedule, same commit-step order,
    bit-identical per round — but all ``S`` commit steps execute inside one
    ``pallas_call`` with the frontier input/output-aliased in VMEM, so the
    δ-buffer flush never round-trips through HBM between commits (see
    :mod:`repro.kernels.round_block`).  ``interpret=None`` auto-dispatches:
    compiled on TPU, interpret-mode emulation elsewhere.
    """
    from repro.kernels.round_block import fused_round_fn

    return fused_round_fn(sched, semiring, row_update, interpret=interpret)


def round_fn_pallas_q(
    sched: DeviceSchedule, semiring: Semiring, row_update, interpret: bool | None = None
) -> Callable:
    """``(x_ext, q) -> x_ext``: the fused Pallas round with query threading.

    Drop-in for :func:`round_fn_q`; ``q``'s pytree leaves ride along as
    VMEM-resident kernel inputs, so the returned callable vmaps for
    :func:`repro.solve.batch.solve_batch` exactly like the XLA round.
    """
    from repro.kernels.round_block import fused_round_fn_q

    return fused_round_fn_q(sched, semiring, row_update, interpret=interpret)


def schedule_args(sched: DeviceSchedule) -> tuple:
    """The schedule's *data* arrays, in :func:`round_fn_q_dyn` argument order.

    Everything else on a :class:`DeviceSchedule` — ``n``, ``P``, ``delta``,
    ``S``, ``M`` — is shape metadata that must stay static for the compiled
    round; these four arrays are the edge content that an
    :class:`repro.graphs.updates.EdgeBatch` can change without changing
    shapes, so the dynamic round takes them as traced inputs.
    """
    return sched.src, sched.val, sched.dst_local, sched.rows


def round_fn_q_dyn(sched: DeviceSchedule, semiring: Semiring, row_update) -> Callable:
    """``(x_ext, q, src, val, dst_local, rows) -> x_ext``: schedule-as-data round.

    Same commit-step semantics as :func:`round_fn_q`, but the schedule arrays
    arrive as traced arguments instead of closure constants — ``sched`` only
    pins the static shape metadata ``(S, P, M, delta, n)``.  This is the
    evolving-graph hot path: after ``Solver.apply_updates`` patches a
    schedule's stripes in place, the same compiled executable replays with the
    new arrays (mirroring how ``sharded_round_fn_q`` already treats its plan),
    so small edge batches never pay a retrace.
    """

    def body(x_ext, q, src, val, dst_local, rows):
        dyn = dataclasses.replace(
            sched, src=src, val=val, dst_local=dst_local, rows=rows
        )
        step = partial(
            _commit_step, sched=dyn, semiring=semiring, row_update=row_update, q=q
        )
        return jax.lax.fori_loop(0, sched.S, step, x_ext)

    return body


def changed_rows(x_prev, x_new):
    """Rows of two extended frontiers (dump slot left out) whose value differs.

    A ``uint32`` scalar, exact for ``n < 2**32``; a row of a matrix frontier
    counts once however many of its features changed.
    """
    ne = x_new[:-1] != x_prev[:-1]
    if ne.ndim > 1:
        ne = jnp.any(ne, axis=tuple(range(1, ne.ndim)))
    return jnp.sum(ne, dtype=jnp.uint32)


def _fixed_point_loop(rnd, residual_fn, x_ext, tol, max_rounds):
    """Rounds of ``rnd: x_ext -> x_ext`` on device until ``residual ≤ tol``.

    Returns the ``lax.while_loop`` carry ``(x_ext, residual, rounds,
    converged, changed)``.  ``changed`` is the running total of
    :func:`changed_rows` as two ``uint32`` words ``(low, high)``, exact to
    ``2**64`` without x64: ``n · max_rounds`` passes ``2**32`` at
    ``n = 2**20`` within 4096 rounds.
    """

    def cond(carry):
        _, _, rounds, converged, _ = carry
        return jnp.logical_and(rounds < max_rounds, jnp.logical_not(converged))

    def body(carry):
        x, _, rounds, _, changed = carry
        x_new = rnd(x)
        res = residual_fn(x[:-1], x_new[:-1]).astype(jnp.float32)
        low = changed[0] + changed_rows(x, x_new)
        high = changed[1] + (low < changed[0]).astype(jnp.uint32)
        return x_new, res, rounds + 1, res <= tol, jnp.stack([low, high])

    init = (
        x_ext,
        jnp.asarray(np.inf, jnp.float32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(False),
        jnp.zeros((2,), jnp.uint32),
    )
    return jax.lax.while_loop(cond, body, init)


def make_solve_fn_q_dyn(
    sched: DeviceSchedule,
    semiring: Semiring,
    row_update,
    residual_fn,
) -> Callable:
    """``(x_ext, q, src, val, dst_local, rows, tol, max_rounds) -> carry``.

    The fused while-loop of :func:`make_solve_fn_q` over the dynamic round:
    one compiled executable per ``(S, P, M, delta)`` shape class serves every
    same-shape mutation of the graph.
    """
    rnd = round_fn_q_dyn(sched, semiring, row_update)

    def solve_loop(x_ext, q, src, val, dst_local, rows, tol, max_rounds):
        return _fixed_point_loop(
            lambda x: rnd(x, q, src, val, dst_local, rows),
            residual_fn,
            x_ext,
            tol,
            max_rounds,
        )

    return solve_loop


def make_solve_fn_q(
    sched: DeviceSchedule,
    semiring: Semiring,
    row_update,
    residual_fn,
    round_builder: Callable = round_fn_q,
) -> Callable:
    """Fused device loop ``(x_ext, q, tol, max_rounds) -> carry``.

    The returned function runs rounds until ``residual ≤ tol`` or
    ``max_rounds``, entirely on device (``lax.while_loop``), and returns the
    carry of :func:`_fixed_point_loop`.  ``tol``/``max_rounds`` are traced
    arguments, so changing them never retraces.

    ``round_builder`` swaps the round implementation the loop iterates —
    :func:`round_fn_q` (the XLA round) or :func:`round_fn_pallas_q` (the
    fused kernel) — while the convergence/residual/counter semantics stay in
    this one place.
    """
    rnd = round_builder(sched, semiring, row_update)

    def solve_loop(x_ext, q, tol, max_rounds):
        return _fixed_point_loop(
            lambda x: rnd(x, q), residual_fn, x_ext, tol, max_rounds
        )

    return solve_loop


def make_solve_fn(
    sched: DeviceSchedule,
    semiring: Semiring,
    row_update,
    residual_fn,
    round_builder: Callable = round_fn_q,
) -> Callable:
    """``(x_ext, tol, max_rounds) -> carry``: query-free fused device loop."""
    fn_q = make_solve_fn_q(
        sched,
        semiring,
        lambda old, red, rows, q: row_update(old, red, rows),
        residual_fn,
        round_builder=round_builder,
    )

    def solve_loop(x_ext, tol, max_rounds):
        return fn_q(x_ext, jnp.zeros((), jnp.int32), tol, max_rounds)

    return solve_loop


@dataclasses.dataclass
class EngineResult:
    x: np.ndarray  # (n,) or (n, F) converged vertex values
    rounds: int
    converged: bool
    flushes: int  # total commit collectives executed
    flush_bytes: int  # total bytes published to the global store
    residuals: list  # per-round convergence residuals
    round_times_s: list  # host-measured wall time per round, compile excluded
    delta: int
    P: int
    compile_time_s: float = 0.0  # trace+compile cost paid by THIS run (0 = warm)
    total_time_s: float = 0.0  # device execution wall time, compile excluded
    changed_rows: int | None = None  # Σ over rounds of rows whose value changed

    @property
    def avg_round_time_s(self) -> float:
        if self.round_times_s:
            return float(np.mean(self.round_times_s))
        return self.total_time_s / self.rounds if self.rounds else 0.0

    @classmethod
    def from_run(
        cls,
        sched: DeviceSchedule,
        semiring: Semiring,
        x_ext,
        *,
        rounds: int,
        converged: bool,
        residuals: list,
        round_times_s: list,
        compile_time_s: float = 0.0,
        total_time_s: float | None = None,
        changed_rows: int | None = None,
    ) -> "EngineResult":
        """Single authority for counter/timing semantics across every runner.

        ``flushes`` counts commit collectives actually executed — ``rounds·S``,
        including the round that detected convergence.  Timings are normalized
        so host-loop and fused-device runs compare like with like: compile cost
        is reported separately in ``compile_time_s`` (never folded into a round
        time), and ``total_time_s`` is post-compile execution wall time, so
        ``rounds · avg_round_time_s ≈ total_time_s`` on both paths.

        Matrix frontiers publish F values per row per commit, so
        ``flush_bytes`` scales by the feature width (``F = 1`` reduces to the
        historical vector accounting, byte for byte).
        """
        F = int(np.prod(np.shape(x_ext)[1:], dtype=np.int64))
        bytes_per = np.dtype(semiring.dtype).itemsize * max(F, 1)
        flushes = rounds * sched.S
        if total_time_s is None:
            total_time_s = float(np.sum(round_times_s)) if round_times_s else 0.0
        return cls(
            x=np.asarray(x_ext[:-1]),
            rounds=rounds,
            converged=converged,
            flushes=flushes,
            flush_bytes=flushes * sched.P * sched.delta * bytes_per,
            residuals=residuals,
            round_times_s=round_times_s,
            delta=sched.delta,
            P=sched.P,
            compile_time_s=compile_time_s,
            total_time_s=total_time_s,
            changed_rows=changed_rows,
        )


def run_host(
    sched: DeviceSchedule,
    semiring: Semiring,
    x0: np.ndarray,
    row_update: Callable,
    residual_fn: Callable,
    tol: float,
    max_rounds: int = 1000,
) -> EngineResult:
    """Host-driven loop: one jitted round per iteration, instrumented.

    ``residual_fn(x_prev, x_new) -> scalar``; converged when ``residual ≤ tol``.
    Used by benchmarks (per-round times/residuals like the paper's Table I).
    The round function is compiled ahead of the loop so every entry of
    ``round_times_s`` is a post-compile measurement.
    """
    x_ext = extend_frontier(x0, semiring)
    t0 = time.perf_counter()
    rnd = jax.jit(round_fn(sched, semiring, row_update)).lower(x_ext).compile()
    compile_time_s = time.perf_counter() - t0
    return host_loop(
        rnd,
        sched,
        semiring,
        x_ext,
        residual_fn,
        tol,
        max_rounds,
        compile_time_s=compile_time_s,
    )


def host_loop(
    rnd: Callable,
    sched: DeviceSchedule,
    semiring: Semiring,
    x_ext,
    residual_fn: Callable,
    tol: float,
    max_rounds: int,
    compile_time_s: float = 0.0,
) -> EngineResult:
    """The host-driven convergence loop over a compiled round ``x_ext -> x_ext``.

    Shared by :func:`run_host` and every :class:`repro.solve.Solver` backend
    that steps rounds from the host (host + sharded) — one copy of the
    timing/stopping semantics.
    """
    residuals, times = [], []
    converged = False
    rounds = changed = 0
    for rounds in range(1, max_rounds + 1):
        # chaos hook at the natural recovery boundary: between committed
        # rounds, with `round` = rounds already executed (0-based)
        fire("solver.round", round=rounds - 1)
        t0 = time.perf_counter()
        x_new = rnd(x_ext)
        x_new.block_until_ready()
        times.append(time.perf_counter() - t0)
        res, c = jax.device_get(
            (residual_fn(x_ext[:-1], x_new[:-1]), changed_rows(x_ext, x_new))
        )
        res = float(res)
        residuals.append(res)
        changed += int(c)
        x_ext = x_new
        if res <= tol:
            converged = True
            break
    return EngineResult.from_run(
        sched,
        semiring,
        x_ext,
        rounds=rounds,
        converged=converged,
        residuals=residuals,
        round_times_s=times,
        compile_time_s=compile_time_s,
        changed_rows=changed,
    )


def execute_solve_fn(
    fn: Callable,
    sched: DeviceSchedule,
    semiring: Semiring,
    x_ext,
    q,
    tol: float,
    max_rounds: int,
    compile_time_s: float = 0.0,
) -> EngineResult:
    """Run a compiled fused loop and normalize its result.

    ``fn`` is a compiled :func:`make_solve_fn_q` (pass its ``q``) or
    :func:`make_solve_fn` (pass ``q=None``).  Shared by :func:`run_jit` and
    the Solver's jit backend — one copy of the execution/timing semantics.
    """
    tol_a = jnp.asarray(tol, jnp.float32)
    mr_a = jnp.asarray(max_rounds, jnp.int32)
    args = (x_ext, tol_a, mr_a) if q is None else (x_ext, q, tol_a, mr_a)
    t0 = time.perf_counter()
    x_out, *scalars = fn(*args)
    x_out.block_until_ready()
    total_time_s = time.perf_counter() - t0
    res, rounds, converged, (low, high) = jax.device_get(scalars)
    return EngineResult.from_run(
        sched,
        semiring,
        x_out,
        rounds=int(rounds),
        converged=bool(converged),
        residuals=[float(res)],
        round_times_s=[],
        compile_time_s=compile_time_s,
        total_time_s=total_time_s,
        changed_rows=int(low) + (int(high) << 32),
    )


def run_jit(
    sched: DeviceSchedule,
    semiring: Semiring,
    x0: jnp.ndarray,
    row_update: Callable,
    residual_fn: Callable,
    tol: float,
    max_rounds: int = 1000,
) -> EngineResult:
    """Fully fused device loop (``lax.while_loop``) — production path."""
    x_ext = extend_frontier(x0, semiring)
    tol_a = jnp.asarray(tol, jnp.float32)
    mr_a = jnp.asarray(max_rounds, jnp.int32)
    jitted = jax.jit(make_solve_fn(sched, semiring, row_update, residual_fn))
    t0 = time.perf_counter()
    fn = jitted.lower(x_ext, tol_a, mr_a).compile()
    compile_time_s = time.perf_counter() - t0
    return execute_solve_fn(
        fn, sched, semiring, x_ext, None, tol, max_rounds, compile_time_s=compile_time_s
    )
