"""The :class:`Solver` — one entry point over every backend, frontier, and δ.

A solver binds ``(graph, problem, n_workers)`` and owns two caches:

* **schedule cache** — :class:`DeviceSchedule` per resolved δ, so repeated
  queries never rebuild stripes;
* **compile cache**  — AOT-compiled round / fused-loop executables per
  ``(backend, frontier, δ)``, so repeated queries never retrace.

``delta`` accepts the paper's three disciplines by name (``"sync"``,
``"async"``), an explicit integer (``"delayed"``), or ``"auto"``, which probes
the sync/async round counts and asks the analytic δ cost model
(:mod:`repro.core.delta_model`) for δ*.  ``backend`` selects host-driven
rounds (instrumented, per-round residuals), the fused ``lax.while_loop``
device path (``"jit"`` iterates the XLA round; ``"pallas"`` iterates the
one-kernel fused round from :mod:`repro.kernels.round_block`, which keeps
the frontier VMEM-resident across all S commit steps), or the ``shard_map``
multi-device engine from :mod:`repro.dist.engine_sharded`; ``frontier``
selects between the replicated frontier (exactness-first, O(P·δ) wire per
commit) and the owner-computes sharded frontier with halo exchange
(O(boundary) wire, graphs larger than one device).  Valid combinations are
the table :data:`BACKEND_FRONTIERS`; the fastest multi-device path is
``backend="pallas", frontier="halo"`` — per-shard fused kernels under
``shard_map`` — optionally with ``halo_dtype ∈ {"f32", "int8", "fp8"}``
shrinking the per-commit halo wire ~4× via error-feedback quantization.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.delta_model import fit_delta_model, refit_delta_models
from repro.core.engine import (
    MIN_CHUNK,
    DeviceSchedule,
    EngineResult,
    execute_solve_fn,
    extend_frontier,
    host_loop,
    make_schedule,
    make_solve_fn_q,
    make_solve_fn_q_dyn,
    round_fn_pallas_q,
    round_fn_q,
    round_fn_q_dyn,
    schedule_args,
)
from repro.ft.degrade import Degradation, degradation_ladder
from repro.ft.inject import fire
from repro.graphs.formats import (
    CSRGraph,
    assemble_stripe_schedule,
    build_worker_stripe,
)
from repro.graphs.partition import PARTITION_METHODS, Partition
from repro.solve.problem import Problem

__all__ = ["Solver", "BACKENDS", "BACKEND_FRONTIERS", "FRONTIERS", "HALO_DTYPES"]

BACKENDS = ("host", "jit", "pallas", "sharded")
FRONTIERS = ("replicated", "halo")

#: The single source of truth for which frontier each backend supports.
#: host/jit iterate single-device rounds and never shard the frontier;
#: pallas runs halo via per-shard fused kernels under shard_map; sharded
#: runs either discipline in plain XLA.
BACKEND_FRONTIERS = {
    "host": ("replicated",),
    "jit": ("replicated",),
    "pallas": ("replicated", "halo"),
    "sharded": ("replicated", "halo"),
}

#: Wire dtypes for the fused halo exchange (pallas + halo only).
HALO_DTYPES = ("f32", "int8", "fp8")

# Round builders for the two fused-loop backends: same while-loop, same
# convergence/residual/counter semantics — only the round implementation
# differs (XLA commit steps vs the one-kernel VMEM-resident round).
_FUSED_ROUND_BUILDERS = {"jit": round_fn_q, "pallas": round_fn_pallas_q}

_NO_QUERY = np.zeros((), dtype=np.int32)  # dummy q for query-free problems
_STRIPES = ("src", "val", "dst_local", "rows")  # the schedule arrays placed on device


class Solver:
    """Reusable solver for one ``(graph, problem)`` pair.

    ``solve()`` answers a query; ``delta=`` / ``backend=`` / ``frontier=``
    per call override the construction defaults.  All schedules, halo plans,
    and compiled executables are cached on the instance — a second ``solve()``
    with the same ``(δ, backend, frontier)`` performs zero schedule builds and
    zero retraces (see ``stats``).

    ``cache_dir=`` extends both caches across *processes*: schedules, halo
    plans, the fitted δ-model, and AOT-exported executables persist to a
    content-addressed store (:mod:`repro.persist`), so a second process
    pointed at the same directory constructs warm — zero stripe builds, zero
    retraces, results bit-identical to cold.  Every solve also logs its
    ``(δ, rounds, time)`` to the store; ``reprobe_every=N`` refits the
    δ-model from those observations every N solves and migrates
    ``delta="auto"`` to the new δ* (see :meth:`reprobe_delta`).
    """

    def __init__(
        self,
        graph: CSRGraph,
        problem: Problem,
        n_workers: int = 8,
        delta="auto",
        backend: str = "jit",
        frontier: str = "replicated",
        halo_dtype: str = "f32",
        partition_method: str = "balanced",
        min_chunk: int = MIN_CHUNK,
        mesh=None,
        mesh_axis: str = "data",
        tol: float | None = None,
        max_rounds: int | None = None,
        cache_dir=None,
        reprobe_every: int | None = None,
        degrade: bool = False,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        self._check_frontier(frontier)
        self._check_halo_dtype(halo_dtype)
        if partition_method not in PARTITION_METHODS:
            raise ValueError(
                f"partition_method must be one of {sorted(PARTITION_METHODS)}, "
                f"got {partition_method!r}"
            )
        self._check_delta(delta)
        self.graph = graph
        self.problem = problem
        self.n_workers = n_workers
        self.default_delta = delta
        self.default_backend = backend
        self.default_frontier = frontier
        self.default_halo_dtype = halo_dtype
        self.partition_method = partition_method
        self.min_chunk = min_chunk
        self.mesh_axis = mesh_axis
        self.tol = problem.tol if tol is None else tol
        self.max_rounds = problem.max_rounds if max_rounds is None else max_rounds
        # degrade=True climbs down repro.ft.degrade.degradation_ladder on
        # kernel/backend faults instead of raising; off by default so tests
        # and benchmarks never mask a real bug behind a silent fallback.
        self.degrade = degrade
        self.degradations: list[Degradation] = []
        self.delta_model = None  # set by the first δ="auto" probe
        self.delta_model_incremental = None  # per-regime fit (evolving graphs)

        self._mesh = mesh
        sr = problem.semiring
        self._sched_graph = (
            graph.with_values(problem.edge_values(graph))
            if problem.edge_values is not None
            else graph
        )
        self._row_update = problem.make_row_update(graph)
        if problem.takes_query:
            self._row_update_q = self._row_update
        else:
            base = self._row_update

            def _row_update_q(old, reduced, rows, q):
                return base(old, reduced, rows)

            self._row_update_q = _row_update_q
        self._bounds = None
        self._partition = None
        self._auto_delta = None
        self._auto_delta_incremental = None
        self._schedules: dict[int, DeviceSchedule] = {}
        self._plans: dict[tuple, object] = {}
        self._compiled: dict[tuple, object] = {}
        self._last_compile_s = 0.0
        self._last_x = None  # fixed point of the most recent solve (host copy)
        self._last_report = None  # UpdateReport of the most recent apply_updates
        self.stats = {
            "solves": 0,
            "schedule_builds": 0,
            "plan_builds": 0,
            "stripe_builds": 0,
            "stripe_loads": 0,
            "traces": 0,
            "compiles": 0,
            "compile_time_s": 0.0,
            "cache_loads": 0,
            "degradations": 0,
        }
        self.reprobe_every = reprobe_every
        self._obs_since_refit = 0
        self._reprobing = False
        self._cache_dir = cache_dir
        if problem.takes_query:
            self._q_template = (
                problem.default_query(graph)
                if problem.default_query is not None
                else np.zeros((graph.n,), dtype=sr.dtype)
            )
        else:
            self._q_template = _NO_QUERY
        self.persist = None
        if cache_dir is not None:
            self.persist = self._make_persist()
            self._warm_from_persist()

    def _make_persist(self):
        """The content-addressed store namespace for the *current* graph."""
        from repro.persist import SolverCache

        return SolverCache.for_solver(
            self._cache_dir,
            self._sched_graph,
            self.problem,
            self._row_update_q,
            self._q_template,
            self.n_workers,
            self.partition_method,
            self.min_chunk,
            self.tol,
            self.max_rounds,
        )

    def _warm_from_persist(self):
        """Load the δ-model eagerly — the one entry with no lazy fallback.

        ``delta="auto"`` then resolves to the persisted (possibly migrated)
        δ* without running a single probe solve.  Schedules, halo plans, and
        executables stay lazy: :meth:`schedule`, :meth:`frontier_plan`, and
        :meth:`compile_cached` each consult the store on an in-memory miss,
        so a warm process deserializes only the δ it actually serves (the
        probe-δ schedules on disk never cost startup time or device memory).
        """
        loaded = self.persist.load_delta_model()
        if loaded is not None:
            self.delta_model, best = loaded
            self._auto_delta = int(min(best, self.block_size))
            self.stats["cache_loads"] += 1
        loaded_inc = self.persist.load_delta_model(regime="incremental")
        if loaded_inc is not None:
            self.delta_model_incremental, best_inc = loaded_inc
            self._auto_delta_incremental = int(min(best_inc, self.block_size))

    # ------------------------------------------------------------------ #
    # δ resolution + schedule/plan caches
    # ------------------------------------------------------------------ #
    @property
    def bounds(self) -> np.ndarray:
        """The (P + 1,) contiguous block bounds of ``partition_method``."""
        if self._bounds is None:
            self._bounds = PARTITION_METHODS[self.partition_method](
                self._sched_graph, self.n_workers
            )
        return self._bounds

    @property
    def block_size(self) -> int:
        """Max worker block size B — the sync δ and the upper clamp."""
        return int(np.diff(self.bounds).max())

    def partition(self) -> Partition:
        """The cached :class:`Partition` (owner map, halo sets, edge cut)."""
        if self._partition is None:
            self._partition = Partition.from_bounds(self._sched_graph, self.bounds)
        return self._partition

    @staticmethod
    def _check_delta(delta):
        if isinstance(delta, str) and delta not in ("sync", "async", "auto"):
            raise ValueError(
                f"delta must be 'sync', 'async', 'auto', or an int, got {delta!r}"
            )

    @staticmethod
    def _check_frontier(frontier):
        if frontier not in FRONTIERS:
            raise ValueError(f"frontier must be one of {FRONTIERS}, got {frontier!r}")

    @staticmethod
    def _check_halo_dtype(halo_dtype):
        if halo_dtype not in HALO_DTYPES:
            raise ValueError(
                f"halo_dtype must be one of {HALO_DTYPES}, got {halo_dtype!r}"
            )

    def resolve_delta(self, delta=None) -> int:
        """Normalize ``delta ∈ {None, 'sync', 'async', 'auto', int}`` to rows."""
        if delta is None:
            delta = self.default_delta
        self._check_delta(delta)
        B = self.block_size
        if delta == "sync":
            return B
        if delta == "async":
            return min(self.min_chunk, B)
        if delta == "auto":
            if self._auto_delta is None:
                self._auto_delta = self._probe_auto_delta()
            return self._auto_delta
        return int(min(max(int(delta), 1), B))

    def resolve_frontier(self, frontier=None, backend: str | None = None) -> str:
        """Normalize the frontier knob against :data:`BACKEND_FRONTIERS`.

        An *explicit* ``frontier`` a backend does not support is an error
        naming the backends that do; an unsupported construction default
        silently falls back to ``"replicated"`` (every backend's first entry)
        so δ="auto" host probes keep working on halo solvers.
        """
        explicit = frontier is not None
        if frontier is None:
            frontier = self.default_frontier
        self._check_frontier(frontier)
        if backend is not None and frontier not in BACKEND_FRONTIERS[backend]:
            if explicit:
                supported = [
                    b for b in reversed(BACKENDS) if frontier in BACKEND_FRONTIERS[b]
                ]
                wants = " or ".join(f"backend={b!r}" for b in supported)
                raise ValueError(
                    f"frontier={frontier!r} requires {wants}, got {backend!r}"
                )
            return "replicated"
        return frontier

    def resolve_halo_dtype(
        self, halo_dtype=None, backend: str | None = None, frontier: str | None = None
    ) -> str:
        """Normalize the halo wire dtype; quantization is pallas+halo only.

        The quantized exchange lives in the fused halo round, so an
        *explicit* low-precision ``halo_dtype`` on any other (backend,
        frontier) pair is an error; a low-precision construction default
        silently resolves to ``"f32"`` there (exact paths stay exact).
        """
        explicit = halo_dtype is not None
        if halo_dtype is None:
            halo_dtype = self.default_halo_dtype
        self._check_halo_dtype(halo_dtype)
        if halo_dtype != "f32" and not (backend == "pallas" and frontier == "halo"):
            if explicit:
                raise ValueError(
                    f"halo_dtype={halo_dtype!r} requires backend='pallas', "
                    f"frontier='halo'; got backend={backend!r}, "
                    f"frontier={frontier!r}"
                )
            return "f32"
        return halo_dtype

    def _probe_auto_delta(self) -> int:
        """Fit the δ cost model from two measured probes (sync + finest δ)."""
        r_sync = self.solve(delta="sync", backend="host")
        r_async = self.solve(delta="async", backend="host")
        self.delta_model = fit_delta_model(
            self._sched_graph,
            self.n_workers,
            r_sync.rounds,
            r_async.rounds,
            delta_min=min(self.min_chunk, self.block_size),
            bytes_per_elem=np.dtype(self.problem.semiring.dtype).itemsize,
        )
        best = min(self.delta_model.best_delta(), self.block_size)
        if self.persist is not None:
            self.persist.save_delta_model(self.delta_model, best)
        return best

    def reprobe_delta(self) -> tuple[int, int]:
        """Refit the δ-model from logged observations and migrate δ*.

        Pulls every production ``(δ, rounds)`` datapoint accumulated in the
        persistent store — unbatched solves and batched ones alike (batch
        round counts are max-over-queries, a conservative upper bound that
        still orders δ correctly, and in a serving process they are the only
        traffic there is) — refits via
        :func:`repro.core.delta_model.refit_delta_model`, and repoints
        ``delta="auto"`` at the new δ*.  Nothing is dropped:
        schedules and compiled executables are keyed by *numeric* δ, so the
        old δ*'s entries (and any explicit-δ neighbors) stay warm in memory
        and on disk — migration only changes what ``"auto"`` resolves to.
        Returns ``(old_delta_star, new_delta_star)``.
        """
        if self.persist is None:
            raise ValueError("reprobe_delta requires a Solver(cache_dir=...)")
        self._reprobing = True
        try:
            old = self.resolve_delta("auto")  # probes or loads the base model
            obs = self.persist.load_observations()
            models = refit_delta_models(self.delta_model, obs)
            self.delta_model = models.get("cold", self.delta_model)
            new = int(min(self.delta_model.best_delta(), self.block_size))
            self._auto_delta = new
            self._obs_since_refit = 0
            self.persist.save_delta_model(self.delta_model, new)
            if "incremental" in models:
                inc = models["incremental"]
                self.delta_model_incremental = inc
                inc_best = int(min(inc.best_delta(), self.block_size))
                self._auto_delta_incremental = inc_best
                self.persist.save_delta_model(inc, inc_best, regime="incremental")
            return old, new
        finally:
            self._reprobing = False

    def _record_observation(
        self, delta: int, rounds: int, total_time_s: float, backend: str,
        kind: str = "solve", regime: str = "cold",
    ):
        """Log one observed (δ, rounds, time); maybe trigger a refit."""
        if self.persist is None:
            return
        self.persist.record_observation(
            delta, rounds, total_time_s, backend=backend, kind=kind, regime=regime
        )
        self._obs_since_refit += 1
        if (
            self.reprobe_every is not None
            and self.default_delta == "auto"
            and self._obs_since_refit >= self.reprobe_every
            # never recurse out of the δ="auto" probe solves (no fitted model
            # yet) or out of a refit already in flight
            and self._auto_delta is not None
            and not self._reprobing
        ):
            self.reprobe_delta()

    def schedule(self, delta=None) -> DeviceSchedule:
        """The cached device schedule for ``delta`` (build on first use).

        Resolution order: in-memory → whole-schedule npz → **per-worker
        stripes** from the shared content-addressed store (evolving-graph
        path: after a mutation the namespace changes, so the whole-schedule
        entry misses, but every stripe whose block the batch didn't touch
        still hits by content digest — only the touched stripes build cold).
        ``schedule_builds`` counts schedules with ≥ 1 cold stripe, preserving
        the warm-start gate's "zero builds" meaning; ``stripe_builds`` /
        ``stripe_loads`` break the same event down per worker.

        A miss is the span ``repro.schedule.build``: the schedule is made on
        the host, then placed on the device in its child span
        ``repro.schedule.put``, which ends once the stripes are there.
        """
        delta_eff = self.resolve_delta(delta)
        sched = self._schedules.get(delta_eff)
        if sched is not None:
            return sched
        with spans.span("repro.schedule.build"):
            if self.persist is not None:
                sched = self.persist.load_schedule(delta_eff, put=np.asarray)
                if sched is not None:
                    self.stats["cache_loads"] += 1
                else:
                    sched = self._schedule_from_stripes(delta_eff)
            if sched is None:
                sched = make_schedule(
                    self._sched_graph,
                    self.n_workers,
                    delta_eff,
                    self.problem.semiring,
                    mode="delayed",
                    min_chunk=self.min_chunk,
                    bounds=self.bounds,
                    put=np.asarray,
                )
                self.stats["schedule_builds"] += 1
                if self.persist is not None:
                    self.persist.save_schedule(sched)
            with spans.span("repro.schedule.put"):
                put = self._schedule_put()
                sched = dataclasses.replace(
                    sched, **{k: put(getattr(sched, k)) for k in _STRIPES}
                )
                jax.block_until_ready(schedule_args(sched))
        self._schedules[delta_eff] = sched
        return sched

    def _schedule_from_stripes(self, delta_eff: int) -> DeviceSchedule:
        """Assemble the schedule stripe-by-stripe through the shared store.

        The schedule is returned on the host, for :meth:`schedule` to place.
        """
        from repro.persist.keys import stripe_fingerprint

        bounds = self.bounds
        pad_val = self.problem.semiring.pad_edge_val
        B = self.block_size
        delta_eff = int(min(delta_eff, B))
        S = -(-B // delta_eff)  # ceil — same clamp as build_stripe_schedule
        stripes, built = [], 0
        for w in range(self.n_workers):
            lo, hi = int(bounds[w]), int(bounds[w + 1])
            digest = stripe_fingerprint(
                self._sched_graph, lo, hi, S, delta_eff, pad_val
            )
            stripe = self.persist.load_stripe(digest)
            if stripe is None:
                stripe = build_worker_stripe(
                    self._sched_graph, lo, hi, S, delta_eff, pad_val
                )
                self.persist.save_stripe(digest, stripe)
                self.stats["stripe_builds"] += 1
                built += 1
            else:
                self.stats["stripe_loads"] += 1
            stripes.append(stripe)
        host = assemble_stripe_schedule(
            self._sched_graph, bounds, delta_eff, pad_val, stripes
        )
        sched = DeviceSchedule.from_stripes(host, np.asarray)
        if built:
            self.stats["schedule_builds"] += 1
        else:
            self.stats["cache_loads"] += 1
        self.persist.save_schedule(sched)
        return sched

    def _schedule_put(self):
        """How schedule arrays reach the device (see :class:`DeviceSchedule`).

        The sharded backend places them straight into the worker-axis
        sharding its rounds read them in, so each chip receives only its own
        workers' stripes and no chip ever holds the whole schedule; every
        other backend keeps them whole on the default device.
        """
        if self.default_backend != "sharded":
            return jnp.asarray
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        sharding = NamedSharding(self._default_mesh(), P(None, self.mesh_axis, None))
        return lambda a: jax.device_put(a, sharding)

    def frontier_plan(self, sched: DeviceSchedule):
        """The cached owner-computes halo plan for ``sched`` on this mesh.

        Mirrors :meth:`schedule`'s tiers: in-memory → whole-plan npz →
        per-shard pieces from the shared content-addressed store (only the
        shards whose workers a mutation touched rebuild; the global assembly
        — exchange indices, gather maps — is recomputed cheaply either way).
        ``plan_builds`` counts plans with ≥ 1 cold shard.  Plans are built on
        the host and placed shard by shard onto the mesh
        (:meth:`FrontierPlan.placed`).
        """
        from repro.dist.compat import mesh_axis_sizes
        from repro.dist.engine_sharded import (
            assemble_frontier_plan,
            build_plan_shard,
            make_frontier_plan,
            plan_shard_bounds,
        )

        mesh = self._default_mesh()
        D = mesh_axis_sizes(mesh)[self.mesh_axis]
        key = (sched.delta, D)
        if key in self._plans:
            return self._plans[key]
        plan = None
        if self.persist is not None:
            plan = self.persist.load_plan(sched.delta, D)
            if plan is not None:
                self.stats["cache_loads"] += 1
        if plan is None and self.persist is not None and sched.P % D == 0:
            from repro.persist.keys import plan_shard_fingerprint

            vb = plan_shard_bounds(sched, D)
            P_loc = sched.P // D
            pieces, built = [], 0
            for d in range(D):
                w0, w1 = d * P_loc, (d + 1) * P_loc
                digest = plan_shard_fingerprint(
                    sched, int(vb[d]), int(vb[d + 1]), w0, w1
                )
                piece = self.persist.load_plan_shard(digest)
                if piece is None:
                    piece = build_plan_shard(
                        sched, int(vb[d]), int(vb[d + 1]), w0, w1
                    )
                    self.persist.save_plan_shard(digest, piece)
                    built += 1
                pieces.append(piece)
            plan = assemble_frontier_plan(sched, D, pieces)
            if built:
                self.stats["plan_builds"] += 1
            else:
                self.stats["cache_loads"] += 1
            self.persist.save_plan(plan)
        if plan is None:
            plan = make_frontier_plan(sched, D)
            self.stats["plan_builds"] += 1
            if self.persist is not None:
                self.persist.save_plan(plan)
        plan = plan.placed(mesh, self.mesh_axis)
        self._plans[key] = plan
        return plan

    # ------------------------------------------------------------------ #
    # compile cache
    # ------------------------------------------------------------------ #
    def _traced(self, fn):
        """Wrap ``fn`` so executions of its *trace* are counted in stats."""

        def wrapped(*args):
            self.stats["traces"] += 1
            return fn(*args)

        return wrapped

    def compile_cached(self, key: tuple, fn, *args, portable: bool = True):
        """AOT-lower + compile ``fn`` for ``args``' shapes, once per ``key``.

        Resolution order: in-memory executable → persistent store (a
        deserialized :mod:`jax.export` blob — compiling it replays StableHLO
        and never re-traces ``fn``, so warm processes stay at zero ``traces``)
        → fresh trace+compile, which is then exported back to the store
        (best-effort; the export re-traces once, a one-time cold cost that
        buys every later process a zero-trace start).  Callers compiling
        shard_map programs pass ``portable=False``: a multi-device export
        pins its device assignment and could never be loaded, so the store
        is skipped entirely instead of computing an export to discard.
        """
        cached = self._compiled.get(key)
        if cached is not None:
            self._last_compile_s = 0.0
            return cached
        t0 = time.perf_counter()
        if self.persist is not None and portable:
            loaded = self.persist.load_executable(key, args)
            if loaded is not None:
                try:
                    cached = jax.jit(loaded).lower(*args).compile()
                except Exception:
                    # a blob can deserialize yet refuse to lower (jax.export
                    # checks platform here, not at deserialize) — e.g. a
                    # CPU-built cache shared to a TPU host.  A miss, not an
                    # error: fall through to the fresh trace below.
                    cached = None
                if cached is not None:
                    self._last_compile_s = time.perf_counter() - t0
                    self._compiled[key] = cached
                    self.stats["cache_loads"] += 1
                    self.stats["compile_time_s"] += self._last_compile_s
                    return cached
        cached = jax.jit(self._traced(fn)).lower(*args).compile()
        self._last_compile_s = time.perf_counter() - t0
        self._compiled[key] = cached
        self.stats["compiles"] += 1
        self.stats["compile_time_s"] += self._last_compile_s
        if self.persist is not None and portable:
            self.persist.save_executable(key, fn, args)
        return cached

    # ------------------------------------------------------------------ #
    # inputs
    # ------------------------------------------------------------------ #
    def _x_ext(self, x0):
        """Append the dump slot to ``x0`` — vector ``(n,)`` or matrix ``(n, F)``.

        A 1-D frontier takes the historical vector path bit-for-bit; a 2-D
        frontier threads its trailing feature axis through every backend.
        ``(n, 1)`` is accepted even for scalar problems — the degenerate
        matrix engine is the bit-identity test surface.
        """
        sr = self.problem.semiring
        if x0 is None:
            x0 = self.problem.x0(self.graph)
        x0 = jnp.asarray(x0, dtype=sr.dtype)
        n = self.graph.n
        if not (x0.shape == (n,) or (x0.ndim == 2 and x0.shape[0] == n)):
            raise ValueError(
                f"x0 must have shape ({n},) or ({n}, F), got {x0.shape}"
            )
        return extend_frontier(x0, sr)

    @staticmethod
    def _fkey(x_ext) -> tuple:
        """Compile-key suffix for the frontier's feature shape.

        ``()`` for vector frontiers keeps every pre-existing cache key —
        and every persisted executable keyed by it — byte-identical;
        matrix frontiers append ``("F", F)`` so a ``(n,)`` and ``(n, F)``
        solve never share an executable.
        """
        return () if x_ext.ndim == 1 else ("F", int(x_ext.shape[-1]))

    def resolve_query(self, q):
        """Normalize the per-query parameter pytree (dummy for query-free)."""
        if not self.problem.takes_query:
            if q is not None:
                raise ValueError(f"problem {self.problem.name!r} takes no query")
            return jnp.asarray(_NO_QUERY)
        if q is None:
            if self.problem.default_query is None:
                raise ValueError(f"problem {self.problem.name!r} needs q=")
            q = self.problem.default_query(self.graph)
        return jax.tree_util.tree_map(jnp.asarray, q)

    # ------------------------------------------------------------------ #
    # solve
    # ------------------------------------------------------------------ #
    def solve(
        self,
        x0=None,
        *,
        q=None,
        delta=None,
        backend: str | None = None,
        frontier: str | None = None,
        halo_dtype: str | None = None,
        tol: float | None = None,
        max_rounds: int | None = None,
        regime: str = "cold",
    ) -> EngineResult:
        """Run to convergence; returns the engine's instrumented result.

        ``regime`` tags the persisted observation row (``"cold"`` for from-
        scratch solves, ``"incremental"`` when :meth:`resolve` seeds from a
        prior fixed point) so the δ-model learns each curve separately.

        With ``degrade=True`` (constructor knob) a kernel/backend fault does
        not propagate: the solve retries one rung down the degradation
        ladder (halo → replicated, then pallas/sharded → jit → host),
        recording a :class:`repro.ft.degrade.Degradation` per fallback in
        ``self.degradations``.  Because every backend computes bit-identical
        rounds, a degraded solve returns the same answer, only slower.
        """
        with spans.span("repro.solve") as attrs:
            backend = backend or self.default_backend
            if backend not in BACKENDS:
                raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
            frontier = self.resolve_frontier(frontier, backend)
            halo_dtype = self.resolve_halo_dtype(halo_dtype, backend, frontier)
            tol = self.tol if tol is None else tol
            max_rounds = self.max_rounds if max_rounds is None else max_rounds
            sched = self.schedule(delta)
            x_ext = self._x_ext(x0)
            q = self.resolve_query(q)
            self.stats["solves"] += 1
            attempts = (
                degradation_ladder(backend, frontier)
                if self.degrade
                else [(backend, frontier)]
            )
            result = None
            for rung, (b, f) in enumerate(attempts):
                hd = halo_dtype if rung == 0 else self.resolve_halo_dtype(None, b, f)
                try:
                    result = self._solve_once(
                        b, f, hd, sched, x_ext, q, tol, max_rounds
                    )
                    break
                except (ValueError, TypeError):
                    raise  # caller errors — never mask these behind a fallback
                except Exception as err:
                    if rung + 1 == len(attempts):
                        raise
                    nb, nf = attempts[rung + 1]
                    self.degradations.append(
                        Degradation(
                            site="solve",
                            from_backend=b,
                            from_frontier=f,
                            to_backend=nb,
                            to_frontier=nf,
                            error=repr(err),
                            rung=rung + 1,
                        )
                    )
                    self.stats["degradations"] += 1
            self._last_x = np.asarray(result.x)
            self._record_observation(
                sched.delta, result.rounds, result.total_time_s, backend, regime=regime
            )
            attrs.update(
                rounds=result.rounds,
                changed_rows=result.changed_rows,
                row_updates=result.rounds * self.graph.n,
            )
        return result

    def _solve_once(
        self, backend, frontier, halo_dtype, sched, x_ext, q, tol, max_rounds
    ) -> EngineResult:
        """One dispatch at a fixed (backend, frontier) rung — the fault domain
        the degradation ladder retries."""
        fire("kernel.dispatch", backend=backend, frontier=frontier)
        if backend in _FUSED_ROUND_BUILDERS and frontier != "halo":
            return self._solve_fused(backend, sched, x_ext, q, tol, max_rounds)
        if backend == "host":
            rnd = self._compiled_round(sched, x_ext, q, "host")
        else:
            rnd = self._compiled_round(sched, x_ext, q, backend, frontier, halo_dtype)
        return self._host_loop(sched, rnd, x_ext, tol, max_rounds)

    def _solve_fused(self, backend, sched, x_ext, q, tol, max_rounds) -> EngineResult:
        """The fused ``lax.while_loop`` path: ``backend ∈ {"jit", "pallas"}``.

        The jit backend compiles the *dynamic-schedule* loop — schedule
        arrays are call arguments, keyed by their shape class ``(δ, S, M)``
        — so an :meth:`apply_updates` that patches stripes in place replays
        the same executable with the new arrays, zero retraces.  The pallas
        kernel bakes the schedule into its grid, so it keeps the closure
        form (mutation drops its cache entry).
        """
        sr = self.problem.semiring
        fk = self._fkey(x_ext)
        if backend == "jit":
            sargs = schedule_args(sched)
            fn = self.compile_cached(
                ("dyn", backend, sched.delta, sched.S, sched.M) + fk,
                make_solve_fn_q_dyn(
                    sched, sr, self._row_update_q, self.problem.residual
                ),
                x_ext,
                q,
                *sargs,
                jnp.asarray(tol, jnp.float32),
                jnp.asarray(max_rounds, jnp.int32),
            )
            compiled = fn

            def fn(x, qq, t, m):
                return compiled(x, qq, *sargs, t, m)

        else:
            fn = self.compile_cached(
                (backend, sched.delta) + fk,
                make_solve_fn_q(
                    sched,
                    sr,
                    self._row_update_q,
                    self.problem.residual,
                    round_builder=_FUSED_ROUND_BUILDERS[backend],
                ),
                x_ext,
                q,
                jnp.asarray(tol, jnp.float32),
                jnp.asarray(max_rounds, jnp.int32),
            )
        return execute_solve_fn(
            fn,
            sched,
            sr,
            x_ext,
            q,
            tol,
            max_rounds,
            compile_time_s=self._last_compile_s,
        )

    def _compiled_round(
        self, sched, x_ext, q, backend, frontier="replicated", halo_dtype="f32"
    ):
        """Cached compiled one-round ``x_ext -> x_ext`` for host/pallas/sharded."""
        sr = self.problem.semiring
        fk = self._fkey(x_ext)
        if backend == "pallas" and frontier == "halo":
            return self._pallas_halo_round(sched, x_ext, q, halo_dtype)
        if backend == "host":
            # dynamic form: survives same-shape schedule mutations, like jit
            sargs = schedule_args(sched)
            rnd = self.compile_cached(
                ("dyn", "host", "round", sched.delta, sched.S, sched.M) + fk,
                round_fn_q_dyn(sched, sr, self._row_update_q),
                x_ext,
                q,
                *sargs,
            )
            return lambda x: rnd(x, q, *sargs)
        if backend == "pallas":
            rnd = self.compile_cached(
                ("pallas", "round", sched.delta) + fk,
                round_fn_pallas_q(sched, sr, self._row_update_q),
                x_ext,
                q,
            )
            return lambda x: rnd(x, q)
        if backend != "sharded":
            raise ValueError(
                f"round backend must be 'host', 'pallas', or 'sharded': {backend!r}"
            )
        mesh = self._default_mesh()
        from repro.dist.compat import mesh_axis_sizes

        D = mesh_axis_sizes(mesh)[self.mesh_axis]
        if frontier == "replicated":
            from repro.dist.engine_sharded import sharded_round_fn_q

            fn = sharded_round_fn_q(
                sched, sr, self._row_update_q, mesh, axis=self.mesh_axis,
                feature_dims=x_ext.ndim - 1,
            )
            args = (sched.src, sched.val, sched.dst_local, sched.rows)
            compiled = self.compile_cached(
                ("sharded", "replicated", sched.delta, D) + fk,
                fn,
                x_ext,
                *args,
                q,
                portable=D == 1,
            )
            return lambda x: compiled(x, *args, q)
        from repro.dist.engine_sharded import frontier_plan_args, frontier_round_ext_fn

        plan = self.frontier_plan(sched)
        fn = frontier_round_ext_fn(
            sched, plan, sr, self._row_update_q, mesh, axis=self.mesh_axis,
            feature_dims=x_ext.ndim - 1,
        )
        args = frontier_plan_args(sched, plan)
        compiled = self.compile_cached(
            ("sharded", "halo", sched.delta, D) + fk, fn, x_ext, q, *args,
            portable=D == 1,
        )
        return lambda x: compiled(x, q, *args)

    def _pallas_halo_round(self, sched, x_ext, q, halo_dtype):
        """The fused halo round: per-shard Pallas kernels under shard_map.

        The error-feedback residuals are loop state, not a function of ``x``,
        so the returned callable carries them across rounds in a closure —
        fresh zeros per call to :meth:`_compiled_round` (i.e. per solve), the
        same lifetime a quantized iterative solve expects.  Cache key
        ``("pallas-halo", δ, dtype, D)``; dropped (not dyn-keyed) on
        :meth:`apply_updates`, exactly like the other baked-plan executables.
        """
        from repro.dist.compat import mesh_axis_sizes
        from repro.dist.engine_sharded import (
            frontier_ef_init,
            frontier_pallas_round_ext_fn,
            frontier_plan_args,
            resolve_halo_dtype,
        )

        sr = self.problem.semiring
        resolve_halo_dtype(halo_dtype, sr)
        mesh = self._default_mesh()
        D = mesh_axis_sizes(mesh)[self.mesh_axis]
        plan = self.frontier_plan(sched)
        fn = frontier_pallas_round_ext_fn(
            sched,
            plan,
            sr,
            self._row_update_q,
            mesh,
            axis=self.mesh_axis,
            halo_dtype=halo_dtype,
            feature_dims=x_ext.ndim - 1,
        )
        args = frontier_plan_args(sched, plan)
        ef0 = frontier_ef_init(plan, x_ext.shape[1:])
        compiled = self.compile_cached(
            ("pallas-halo", sched.delta, halo_dtype, D) + self._fkey(x_ext),
            fn,
            x_ext,
            ef0,
            q,
            *args,
            portable=D == 1,
        )
        state = {"ef": ef0}

        def rnd(x):
            x, state["ef"] = compiled(x, state["ef"], q, *args)
            return x

        # expose the loop-carried error-feedback residuals so checkpointing
        # (repro.ft.elastic) can snapshot/restore/reset them between rounds
        rnd.ef_state = state
        rnd.ef_init = ef0
        return rnd

    def _host_loop(self, sched, rnd, x_ext, tol, max_rounds) -> EngineResult:
        return host_loop(
            rnd,
            sched,
            self.problem.semiring,
            x_ext,
            self.problem.residual,
            tol,
            max_rounds,
            compile_time_s=self._last_compile_s,
        )

    # ------------------------------------------------------------------ #
    # evolving graphs: apply_updates + incremental resolve
    # ------------------------------------------------------------------ #
    def apply_updates(self, batch):
        """Mutate the bound graph in place; returns the ``UpdateReport``.

        Rebinds the problem's row update and edge values to the new graph and
        invalidates **only** what the batch touched: cached schedules keep
        every stripe whose worker block the affected rows miss (patched in
        place, same shapes — the dyn-keyed executables replay without a
        retrace); halo plans and non-dyn executables drop (their index
        arrays / baked constants are stale); the persist namespace re-derives
        from the new graph content, carrying the fitted δ-models over and
        pushing the rebuilt stripes into the shared store so a restarted
        process stays warm everywhere the batch didn't reach.

        The partition bounds are **pinned** across updates: recomputing a
        degree-sensitive partition on the mutated graph would shift every
        block boundary and invalidate all stripes for a one-row change.
        """
        bounds = self.bounds  # pin pre-mutation bounds before swapping graphs
        new_graph, report = self.graph.apply_updates(batch)
        self.graph = new_graph
        problem = self.problem
        self._sched_graph = (
            new_graph.with_values(problem.edge_values(new_graph))
            if problem.edge_values is not None
            else new_graph
        )
        self._row_update = problem.make_row_update(new_graph)
        if problem.takes_query:
            self._row_update_q = self._row_update
        else:
            base = self._row_update

            def _row_update_q(old, reduced, rows, q):
                return base(old, reduced, rows)

            self._row_update_q = _row_update_q
        self._bounds = bounds
        self._partition = None
        self._plans = {}
        self._compiled = {
            k: v for k, v in self._compiled.items() if k and k[0] == "dyn"
        }
        if self.persist is not None:
            old_persist = self.persist
            self.persist = self._make_persist()
            # The observation log follows the *logical* graph across
            # mutations: reprobe_delta needs rounds-vs-δ data accumulated
            # over many small batches, each of which re-derives the
            # namespace but barely moves the curve being fitted.
            old_obs = old_persist.dir / "observations.jsonl"
            new_obs = self.persist.dir / "observations.jsonl"
            if old_obs.exists() and not new_obs.exists():
                try:
                    new_obs.write_bytes(old_obs.read_bytes())
                except OSError:
                    pass
            if self.delta_model is not None and self._auto_delta is not None:
                self.persist.save_delta_model(self.delta_model, self._auto_delta)
            if (
                self.delta_model_incremental is not None
                and self._auto_delta_incremental is not None
            ):
                self.persist.save_delta_model(
                    self.delta_model_incremental,
                    self._auto_delta_incremental,
                    regime="incremental",
                )
        self._patch_schedules(report)
        self._last_report = report
        return report

    def _touched_workers(self, affected_rows) -> np.ndarray:
        """Worker blocks containing any affected destination row."""
        affected = np.asarray(affected_rows, dtype=np.int64)
        if affected.size == 0:
            return np.zeros(0, dtype=np.int64)
        return np.unique(np.searchsorted(self.bounds, affected, side="right") - 1)

    def _patch_schedules(self, report):
        """Rebuild only the touched workers' stripes of every cached schedule.

        A stripe that outgrows the schedule's padded width ``M`` forces that
        δ's schedule to drop for a lazy full rebuild (global re-padding would
        touch every worker anyway); otherwise the patched arrays keep their
        shapes, which is what lets the dyn executables replay compile-free.
        """
        from repro.persist.keys import stripe_fingerprint

        bounds = self.bounds
        pad_val = self.problem.semiring.pad_edge_val
        touched = self._touched_workers(report.affected_rows)
        for delta_eff, sched in list(self._schedules.items()):
            stripes, fits = {}, True
            for w in touched:
                lo, hi = int(bounds[w]), int(bounds[w + 1])
                st = build_worker_stripe(
                    self._sched_graph, lo, hi, sched.S, delta_eff, pad_val
                )
                if st["src"].shape[1] > sched.M:
                    fits = False
                    break
                stripes[int(w)] = st
            if not fits:
                del self._schedules[delta_eff]
                continue
            src = np.array(sched.src)
            val = np.array(sched.val)
            dst_local = np.array(sched.dst_local)
            for w, st in stripes.items():
                m = st["src"].shape[1]
                src[:, w, :] = 0
                src[:, w, :m] = st["src"]
                val[:, w, :] = pad_val
                val[:, w, :m] = st["val"]
                dst_local[:, w, :] = delta_eff
                dst_local[:, w, :m] = st["dst_local"]
                # rows[:, w] is untouched: it depends only on (lo, hi, δ, n)
            put = self._schedule_put()
            self._schedules[delta_eff] = dataclasses.replace(
                sched,
                src=put(src),
                val=put(val),
                dst_local=put(dst_local),
                edges=self._sched_graph.nnz,
                padding_overhead=src.size / max(self._sched_graph.nnz, 1),
            )
            if self.persist is not None:
                for w, st in stripes.items():
                    digest = stripe_fingerprint(
                        self._sched_graph,
                        int(bounds[w]),
                        int(bounds[w + 1]),
                        sched.S,
                        delta_eff,
                        pad_val,
                    )
                    self.persist.save_stripe(digest, st)

    def resolve(
        self,
        updates=None,
        *,
        x0=None,
        q=None,
        delta=None,
        backend: str | None = None,
        frontier: str | None = None,
        tol: float | None = None,
        max_rounds: int | None = None,
    ) -> EngineResult:
        """Incremental re-solve after ``updates`` (an ``EdgeBatch``), seeded
        from the previous fixed point.

        Applies the batch via :meth:`apply_updates`, repairs the prior fixed
        point into a valid warm state (:mod:`repro.evolve.restart` — the
        delete-edge invalidation cone is re-raised for min-plus problems
        before any re-lowering), and converges on the mutated graph.  The
        result equals a cold :meth:`solve` on the mutated graph within tol
        (bit-exact labels for min-plus) in typically far fewer rounds.

        ``x0=`` overrides the warm seed (defaults to this solver's last
        solve's fixed point).  With ``updates=None`` this is a plain warm
        re-solve.  ``delta=None``/``"auto"`` prefers the incremental-regime
        δ* once :meth:`reprobe_delta` has fitted one.
        """
        if x0 is None and self._last_x is None:
            raise ValueError(
                "resolve() warm-starts from the previous fixed point — "
                "call solve() first or pass x0="
            )
        report = None
        if updates is not None:
            report = self.apply_updates(updates)
        x_prev = np.asarray(x0) if x0 is not None else self._last_x
        from repro.evolve.restart import warm_start_state

        y = warm_start_state(
            self.problem,
            self.graph,
            self._sched_graph,
            x_prev,
            batch=updates,
            report=report,
        )
        if (delta is None and self.default_delta == "auto") or delta == "auto":
            if self._auto_delta_incremental is not None:
                delta = self._auto_delta_incremental
        return self.solve(
            y,
            q=q,
            delta=delta,
            backend=backend,
            frontier=frontier,
            tol=tol,
            max_rounds=max_rounds,
            regime="incremental",
        )

    def solve_batch(
        self,
        x0_batch,
        *,
        q=None,
        delta=None,
        backend: str | None = None,
        frontier: str | None = None,
        tol=None,
        max_rounds=None,
        compact_every: int | None = None,
    ):
        """Batched multi-query solve — see :func:`repro.solve.batch.solve_batch`."""
        from repro.solve.batch import solve_batch

        return solve_batch(
            self,
            x0_batch,
            q=q,
            delta=delta,
            backend=backend,
            frontier=frontier,
            tol=tol,
            max_rounds=max_rounds,
            compact_every=compact_every,
        )

    # ------------------------------------------------------------------ #
    # sharded plumbing + introspection
    # ------------------------------------------------------------------ #
    def _default_mesh(self):
        if self._mesh is None:
            from repro.dist.compat import make_mesh

            ndev = len(jax.devices())
            size = math.gcd(self.n_workers, ndev)
            self._mesh = make_mesh(
                (size,), (self.mesh_axis,), devices=jax.devices()[:size]
            )
        return self._mesh

    def round_callable(
        self,
        delta=None,
        backend: str = "host",
        frontier: str | None = None,
        q=None,
        halo_dtype: str | None = None,
    ):
        """The cached compiled one-round ``x_ext -> x_ext`` (tests/benchmarks).

        ``backend`` is ``"host"`` (the single-device XLA round — also what
        the jit backend's fused loop iterates), ``"pallas"`` (the fused
        one-kernel round the pallas backend iterates; with
        ``frontier="halo"`` the per-shard fused halo round), or
        ``"sharded"``; ``frontier`` picks replicated vs halo per
        :data:`BACKEND_FRONTIERS`.
        """
        frontier = self.resolve_frontier(frontier, backend)
        halo_dtype = self.resolve_halo_dtype(halo_dtype, backend, frontier)
        sched = self.schedule(delta)
        return self._compiled_round(
            sched,
            self._x_ext(None),
            self.resolve_query(q),
            backend,
            frontier,
            halo_dtype,
        )
