"""shard_map execution of the delayed-async engine over a worker mesh axis.

Two distribution disciplines, both bit-identical per round to the
single-device ``round_fn`` (same update list, same order, dump slot included
for the replicated path / owned frontier for the sharded one):

* **replicated frontier** (``sharded_round_fn`` / ``sharded_round_fn_q``) —
  every device holds the whole frontier ``x_ext``; each commit all-gathers
  every worker's chunk (O(P·δ) wire per commit) and publishes with exactly
  the reference scatter.  Exactness-first; bounded by one device's memory.

* **sharded frontier with halo exchange** (``frontier_sharded_round_fn``) —
  owner-computes: each device keeps only its owned vertex block plus halo
  copies of the remote vertices its workers read (:class:`FrontierPlan`,
  built on the cut/halo sets of :class:`repro.graphs.partition.Partition`).
  Each commit publishes locally and all-gathers only the *boundary* entries
  other shards need (O(D·H) wire per commit, H = max boundary rows per
  commit step).  The halo copy of a vertex always holds its owner's last
  committed value — exactly what the replicated round reads — so rounds stay
  bit-identical while the frontier spans devices.

* **fused sharded frontier** (``frontier_pallas_round_fn``) — the same
  owner-computes discipline with each shard's commit step fused into one
  Pallas kernel (:func:`repro.kernels.round_block.fused_halo_step_fn`):
  gather/⊗/segment-⊕/row-update/publish and the boundary-row selection all
  run with the shard's frontier slice pinned in VMEM; only the boundary
  all-gather runs in XLA between kernel invocations.  This is the paper's
  thread-local buffer applied at both levels of the hierarchy at once —
  VMEM within a chip, halo across chips.  ``halo_dtype ∈ {"f32","int8",
  "fp8"}`` additionally quantizes the shipped boundary rows with per-shard
  error-feedback residuals, so the gathered elements are genuinely 1-byte
  on the wire (f32 stays bit-identical to the XLA rounds; low-precision
  converges to the same fixed point within quantization tolerance).

The schedule arrays are function arguments (not closure constants) so the
worker axis can be sharded by ``shard_map`` in_specs and the whole round is
AOT-lowerable from ``input_specs_for_engine``.  The *plan* arrays are kept
shard-major (``(D, S, P_loc, ·)``, one block per shard) so plan assembly
never materializes full ``(S, P, M)`` stripe monoliths host-side and the
``shard_map`` in_specs slice them along the leading shard axis.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import DeviceSchedule
from repro.core.semiring import Semiring, edge_products, sorted_segment_reduce
from repro.dist.compat import mesh_axis_sizes
from repro.kernels.round_block import fused_halo_step_fn

__all__ = [
    "FrontierPlan",
    "HALO_DTYPES",
    "assemble_frontier_plan",
    "build_plan_shard",
    "frontier_ef_init",
    "frontier_pallas_round_ext_fn",
    "frontier_pallas_round_fn",
    "frontier_plan_args",
    "frontier_round_ext_fn",
    "frontier_sharded_round_fn",
    "input_specs_for_engine",
    "make_frontier_plan",
    "plan_shard_bounds",
    "resolve_halo_dtype",
    "sharded_round_fn",
    "sharded_round_fn_q",
]

#: Wire dtypes supported for the fused halo exchange.  ``"f32"`` ships the
#: committed boundary rows verbatim (bit-identical rounds); ``"int8"`` /
#: ``"fp8"`` quantize per (shard, commit) with an error-feedback residual so
#: each gathered element is one byte on the wire.
HALO_DTYPES = ("f32", "int8", "fp8")

_HALO_QUANT = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}


def resolve_halo_dtype(halo_dtype: str, semiring: Semiring) -> str:
    """Validate ``halo_dtype`` against :data:`HALO_DTYPES` and the semiring.

    Low-precision halo exchange quantizes in f32, so it is only defined for
    floating-point semirings (min-plus runs on int32 where rounding a path
    length would silently corrupt exactness).
    """
    if halo_dtype not in HALO_DTYPES:
        raise ValueError(
            f"halo_dtype={halo_dtype!r} not supported; choose from {HALO_DTYPES}"
        )
    if halo_dtype != "f32" and not jnp.issubdtype(
        jnp.dtype(semiring.dtype), jnp.floating
    ):
        raise ValueError(
            f"halo_dtype={halo_dtype!r} requires a floating-point semiring, "
            f"got dtype={jnp.dtype(semiring.dtype).name}"
        )
    return halo_dtype


def sharded_round_fn_q(
    sched: DeviceSchedule,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    feature_dims: int = 0,
) -> Callable:
    """Return jit-able ``(x_ext, src, val, dst_local, rows, row_last, q) -> x_ext``.

    One full round (``S`` commit steps) with the worker dimension of the
    schedule sharded over mesh ``axis``; ``x_ext`` and the per-query params
    ``q`` stay replicated.  ``row_update`` is the 4-arg query form
    ``(old, reduced, rows, q) -> new``.  Requires ``sched.P`` divisible by the
    axis size (workers per device is static).

    ``feature_dims`` is the number of trailing feature axes on ``x_ext`` —
    0 for the classic ``(n+1,)`` vector frontier, 1 for ``(n+1, F)`` matrix
    frontiers (the feature axis stays replicated; only the worker axis
    shards).
    """
    axis_size = mesh_axis_sizes(mesh)[axis]
    if sched.P % axis_size != 0:
        raise ValueError(f"P={sched.P} not divisible by |{axis}|={axis_size}")
    passes = sched.passes

    def body(x_ext, src, val, dst_local, rows, row_last, q):
        feat = x_ext.shape[1:]

        def commit_step(s, x):
            src_s = jax.lax.dynamic_index_in_dim(src, s, 0, keepdims=False)
            val_s = jax.lax.dynamic_index_in_dim(val, s, 0, keepdims=False)
            dst_s = jax.lax.dynamic_index_in_dim(dst_local, s, 0, keepdims=False)
            rows_s = jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)
            last_s = jax.lax.dynamic_index_in_dim(row_last, s, 0, keepdims=False)

            gathered = x[src_s]  # (P_loc, M) + feat — committed frontier reads
            contrib = edge_products(semiring, gathered, val_s, dst_s)
            reduced = sorted_segment_reduce(semiring, contrib, dst_s, last_s, passes)
            old = x[rows_s]
            new = row_update(old, reduced, rows_s, q)
            # Flush: gather every worker's chunk, publish with the reference
            # engine's scatter (same updates, same order → bit-identical).
            new_full = jax.lax.all_gather(new, axis, axis=0, tiled=True)
            rows_full = jax.lax.all_gather(rows_s, axis, axis=0, tiled=True)
            return x.at[rows_full.reshape(-1)].set(
                new_full.reshape((-1,) + feat).astype(x.dtype),
                mode="drop",
                unique_indices=False,
            )

        return jax.lax.fori_loop(0, sched.S, commit_step, x_ext)

    sched_spec = P(None, axis, None)
    x_spec = P(*((None,) * (1 + feature_dims)))
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec,) + (sched_spec,) * 5 + (P(),),
        out_specs=x_spec,
        check_vma=False,
    )


def sharded_round_fn(
    sched: DeviceSchedule,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    feature_dims: int = 0,
) -> Callable:
    """Query-free surface: ``(x_ext, src, val, dst_local, rows, row_last) -> x_ext``.

    ``row_update`` is the 3-arg form ``(old, reduced, rows) -> new``.
    """
    fn_q = sharded_round_fn_q(
        sched,
        semiring,
        lambda old, reduced, rows, q: row_update(old, reduced, rows),
        mesh,
        axis,
        feature_dims,
    )

    def fn(x_ext, src, val, dst_local, rows, row_last):
        return fn_q(
            x_ext, src, val, dst_local, rows, row_last, jnp.zeros((), jnp.int32)
        )

    return fn


def input_specs_for_engine(sched: DeviceSchedule, semiring: Semiring) -> tuple:
    """ShapeDtypeStructs matching ``sharded_round_fn``'s signature (AOT path)."""
    SDS = jax.ShapeDtypeStruct
    return (
        SDS((sched.n_slots,), semiring.dtype),
        SDS(sched.src.shape, jnp.int32),
        SDS(sched.val.shape, sched.val.dtype),
        SDS(sched.dst_local.shape, jnp.int32),
        SDS(sched.rows.shape, jnp.int32),
        SDS(sched.row_last.shape, jnp.int32),
    )


# --------------------------------------------------------------------------- #
# Frontier sharding: owner-computes layout + per-commit halo exchange
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class FrontierPlan:
    """Owner-computes layout + halo-exchange indices for one ``(sched, D)``.

    Shard ``d`` (one of ``D`` mesh slots, ``P_loc = P / D`` schedule workers)
    owns vertices ``[vertex_bounds[d], vertex_bounds[d+1])`` and keeps a local
    frontier of length ``L``: owned block, then halo copies of the remote
    vertices its workers read (sorted by global id), then a dump slot at
    ``L - 1`` (absorbing schedule padding, exactly like slot ``n`` of the
    replicated ``x_ext``).

    Per commit step ``s``, shard ``d`` publishes its chunk locally and ships
    the ``≤ H`` committed rows that appear in some other shard's halo
    (``send_idx``); every shard scatters the all-gathered ``(D·H,)`` buffer
    into its own halo slots (``recv_idx``; non-resident and padding entries
    land in the dump slot).

    The index arrays are host numpy when built or loaded; :meth:`placed`
    moves them onto a mesh in the layout the halo round reads them in.
    """

    D: int
    P_loc: int
    L: int
    H: int
    S: int
    delta: int
    n: int
    vertex_bounds: np.ndarray  # (D + 1,) int64
    halo_sizes: np.ndarray  # (D,) int64 — |halo_in| per shard
    boundary_entries_per_round: int  # true (unpadded) halo rows shipped/round
    src_loc: np.ndarray  # (D, S, P_loc, M) int32 — shard-major local src indices
    rows_loc: np.ndarray  # (D, S, P_loc, delta) int32 — shard-major row slots
    send_idx: np.ndarray  # (S, D, H) int32 into the flat (P_loc·delta,) chunk
    recv_idx: np.ndarray  # (S, D, D·H) int32 into the local frontier
    gather_index: np.ndarray  # (D, L) int32 — global slot of each local slot
    owned_flat: np.ndarray  # (n,) int32 — flat (D·L) slot owning each vertex

    def placed(self, mesh, axis: str = "data") -> "FrontierPlan":
        """This plan with its arrays on ``mesh`` in the halo round's layout.

        Shard-major blocks split along their leading shard axis and the
        per-commit exchange indices along their shard axis, so each device
        receives only its own shard straight from the host; the two small
        global maps are replicated.
        """
        block = NamedSharding(mesh, P(axis, None, None, None))
        cell = NamedSharding(mesh, P(None, axis, None))
        whole = NamedSharding(mesh, P())
        return dataclasses.replace(
            self,
            src_loc=jax.device_put(self.src_loc, block),
            rows_loc=jax.device_put(self.rows_loc, block),
            send_idx=jax.device_put(self.send_idx, cell),
            recv_idx=jax.device_put(self.recv_idx, cell),
            gather_index=jax.device_put(self.gather_index, whole),
            owned_flat=jax.device_put(self.owned_flat, whole),
        )

    # ------------------------------------------------------------------ #
    # Wire accounting (the replicated column is the engine's flush_bytes)
    # ------------------------------------------------------------------ #
    def halo_bytes_per_round(self, bytes_per_elem: int = 4) -> int:
        """Bytes each shard receives per round from the halo all-gathers."""
        return self.S * self.D * self.H * bytes_per_elem

    def replicated_bytes_per_round(self, bytes_per_elem: int = 4) -> int:
        """Same-round wire of the replicated flush (S · P · δ elements)."""
        return self.S * self.D * self.P_loc * self.delta * bytes_per_elem

    def scatter_x(self, x_ext) -> jnp.ndarray:
        """Replicated ``(n + 1,)`` frontier → stacked ``(D, L)`` local view."""
        return jnp.asarray(x_ext)[self.gather_index]

    # ------------------------------------------------------------------ #
    # persistence (repro.persist stores plans as plain npz archives)
    # ------------------------------------------------------------------ #
    def to_host_arrays(self) -> dict:
        """Flat ``{name: ndarray}`` dict round-trippable through ``np.savez``."""
        out = {
            f.name: np.asarray(getattr(self, f.name))
            for f in dataclasses.fields(self)
        }
        return out

    @classmethod
    def from_host_arrays(cls, arrays) -> "FrontierPlan":
        """Rebuild from :meth:`to_host_arrays` output (shape-validated)."""
        D, S, H, L = (int(arrays[k]) for k in ("D", "S", "H", "L"))
        plan = cls(
            D=D,
            P_loc=int(arrays["P_loc"]),
            L=L,
            H=H,
            S=S,
            delta=int(arrays["delta"]),
            n=int(arrays["n"]),
            vertex_bounds=np.asarray(arrays["vertex_bounds"], dtype=np.int64),
            halo_sizes=np.asarray(arrays["halo_sizes"], dtype=np.int64),
            boundary_entries_per_round=int(arrays["boundary_entries_per_round"]),
            src_loc=np.asarray(arrays["src_loc"]),
            rows_loc=np.asarray(arrays["rows_loc"]),
            send_idx=np.asarray(arrays["send_idx"]),
            recv_idx=np.asarray(arrays["recv_idx"]),
            gather_index=np.asarray(arrays["gather_index"]),
            owned_flat=np.asarray(arrays["owned_flat"]),
        )
        if (
            plan.send_idx.shape != (S, D, H)
            or plan.recv_idx.shape != (S, D, D * H)
            or plan.gather_index.shape != (D, L)
            or plan.vertex_bounds.shape != (D + 1,)
            or plan.src_loc.shape[:3] != (D, S, plan.P_loc)
            or plan.rows_loc.shape != (D, S, plan.P_loc, plan.delta)
        ):
            raise ValueError("plan arrays inconsistent with (S, D, H, L)")
        return plan

    def gather_x(self, x_loc, dump=None):
        """Stacked ``(D, L)+feat`` local view → ``(n + 1,)+feat`` global frontier."""
        feat = jnp.shape(x_loc)[2:]
        flat = jnp.reshape(x_loc, (-1,) + tuple(feat))
        owned = flat[self.owned_flat]
        if dump is None:
            dump = flat[-1:]
        return jnp.concatenate([owned, dump])


def plan_shard_bounds(sched: DeviceSchedule, n_shards: int) -> np.ndarray:
    """Shard vertex bounds ``vb (D + 1,)`` for ``sched`` over ``n_shards``."""
    if sched.block_bounds is None:
        raise ValueError("sched has no block_bounds (rebuild via make_schedule)")
    bounds = np.asarray(sched.block_bounds, dtype=np.int64)
    D = int(n_shards)
    if sched.P % D != 0:
        raise ValueError(f"P={sched.P} not divisible by D={D}")
    P_loc = sched.P // D
    vb = bounds[::P_loc]
    assert vb.shape == (D + 1,) and vb[-1] == sched.n
    return vb


def build_plan_shard(
    sched: DeviceSchedule, vb_lo: int, vb_hi: int, w0: int, w1: int
) -> dict:
    """One shard's plan piece: halo set + local index arrays (host numpy).

    The unit of targeted plan invalidation: it reads only the shard's own
    worker slices of the schedule (``src``/``dst_local``/``rows`` columns
    ``[w0, w1)``) and its owned interval ``[vb_lo, vb_hi)``, so it can be
    content-addressed (:func:`repro.persist.keys.plan_shard_fingerprint`) and
    reused when a mutation leaves those workers' stripes unchanged.  Dump
    slots are stored as ``-1`` sentinels because the real dump index ``L - 1``
    depends on *every* shard's halo size — :func:`assemble_frontier_plan`
    substitutes it.  Reads its workers through
    :meth:`DeviceSchedule.worker_block`, so a sharded schedule hands over
    only this shard's stripes.
    """
    # int32 throughout: vertex ids and local slots are < n < 2**31, and at
    # scale the (S, P_loc, M) temporaries dominate the host's plan build.
    src_d = sched.worker_block("src", w0, w1)
    real_d = sched.worker_block("dst_local", w0, w1) < sched.delta
    own = real_d & (src_d >= vb_lo) & (src_d < vb_hi)
    rem = real_d & ~own
    remote_src = src_d[rem]
    halo = np.unique(remote_src)
    owned_d = int(vb_hi - vb_lo)

    loc = np.full(src_d.shape, -1, dtype=np.int32)
    loc[own] = src_d[own] - np.int32(vb_lo)
    if halo.size:
        loc[rem] = owned_d + np.searchsorted(halo, remote_src).astype(np.int32)
    rr = sched.worker_block("rows", w0, w1)
    rows_loc = np.where(rr >= sched.n, np.int32(-1), rr - np.int32(vb_lo))
    return {
        "halo": halo.astype(np.int64),
        "src_loc": loc,
        "rows_loc": rows_loc.astype(np.int32),
    }


def make_frontier_plan(sched: DeviceSchedule, n_shards: int) -> FrontierPlan:
    """Build the owner-computes halo plan for ``sched`` over ``n_shards``.

    Halo sets are derived from the schedule's own edge lists (the same cut
    edges :meth:`repro.graphs.partition.Partition.from_bounds` reports, but
    resolved against the padded stripe layout so padding conventions can
    never drift): shard ``d``'s halo is every real source vertex its workers
    gather that lies outside its owned range.
    """
    D = int(n_shards)
    vb = plan_shard_bounds(sched, D)
    P_loc = sched.P // D
    pieces = [
        build_plan_shard(
            sched, int(vb[d]), int(vb[d + 1]), d * P_loc, (d + 1) * P_loc
        )
        for d in range(D)
    ]
    return assemble_frontier_plan(sched, D, pieces)


def assemble_frontier_plan(
    sched: DeviceSchedule, n_shards: int, pieces: list
) -> FrontierPlan:
    """Stitch per-shard pieces into a :class:`FrontierPlan`.

    ``pieces[d]`` is :func:`build_plan_shard`'s dict (freshly built or loaded
    from the content-addressed store).  Everything global — ``L``, ``H``, the
    send/recv exchange indices, ``gather_index``, ``owned_flat`` — is
    recomputed here from the halos plus the schedule's ``rows``; that is the
    cheap, shard-coupled part, so it is never cached piecewise.  Output is
    bit-identical to the monolithic plan build.
    """
    rows = np.asarray(sched.rows)
    S = sched.S
    delta, n, D = sched.delta, sched.n, int(n_shards)
    P_loc = sched.P // D
    vb = plan_shard_bounds(sched, D)
    owned = np.diff(vb)

    halo = [np.asarray(p["halo"], dtype=np.int64) for p in pieces]
    halo_sizes = np.array([h.size for h in halo], dtype=np.int64)
    L = int((owned + halo_sizes).max()) + 1 if D else 1
    dump = L - 1

    # Shard-major (D, S, P_loc, ·): each shard's block is written straight
    # from its piece — no full-width (S, P, M) stripe monolith is ever
    # materialized host-side, and shard_map in_specs slice axis 0 directly.
    src_loc = np.empty((D, S, P_loc, sched.M), dtype=np.int32)
    rows_loc = np.empty((D, S, P_loc, delta), dtype=np.int32)
    for d, p in enumerate(pieces):
        sl, rl = p["src_loc"], p["rows_loc"]
        src_loc[d] = np.where(sl < 0, dump, sl)
        rows_loc[d] = np.where(rl < 0, dump, rl)

    # Boundary traffic: per (step, shard), the committed rows some other
    # shard keeps a halo copy of.  H pads to the worst (step, shard) cell.
    is_boundary = np.zeros(n + 1, dtype=bool)  # slot n (dump) is never shipped
    for h in halo:
        is_boundary[h] = True
    chunks = [
        [
            rows[s, d * P_loc : (d + 1) * P_loc, :].reshape(-1).astype(np.int64)
            for d in range(D)
        ]
        for s in range(S)
    ]
    send_pos = [[np.nonzero(is_boundary[c])[0] for c in chunks[s]] for s in range(S)]
    counts = np.array([[p.size for p in row] for row in send_pos], dtype=np.int64)
    H = max(1, int(counts.max())) if counts.size else 1

    send_idx = np.zeros((S, D, H), dtype=np.int32)
    recv_idx = np.full((S, D, D * H), dump, dtype=np.int32)
    for s in range(S):
        for d in range(D):
            pos = send_pos[s][d]
            send_idx[s, d, : pos.size] = pos
            gv = chunks[s][d][pos]  # global vertices shipped by shard d
            for e in range(D):
                he = halo[e]
                if e == d or he.size == 0 or gv.size == 0:
                    continue
                ins = np.minimum(np.searchsorted(he, gv), he.size - 1)
                hit = he[ins] == gv
                recv_idx[s, e, d * H + np.nonzero(hit)[0]] = owned[e] + ins[hit]

    gather_index = np.full((D, L), n, dtype=np.int32)  # unused slots → dump
    owned_flat = np.zeros(n, dtype=np.int32)
    for d in range(D):
        gather_index[d, : owned[d]] = np.arange(vb[d], vb[d + 1])
        gather_index[d, owned[d] : owned[d] + halo[d].size] = halo[d]
        owned_flat[vb[d] : vb[d + 1]] = d * L + np.arange(owned[d])

    return FrontierPlan(
        D=D,
        P_loc=P_loc,
        L=L,
        H=H,
        S=S,
        delta=delta,
        n=n,
        vertex_bounds=vb,
        halo_sizes=halo_sizes,
        boundary_entries_per_round=int(counts.sum()),
        src_loc=src_loc,
        rows_loc=rows_loc,
        send_idx=send_idx,
        recv_idx=recv_idx,
        gather_index=gather_index,
        owned_flat=owned_flat,
    )


def frontier_sharded_round_fn(
    sched: DeviceSchedule,
    plan: FrontierPlan,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    feature_dims: int = 0,
) -> Callable:
    """Owner-computes round over the sharded frontier ``(D, L)``.

    Returns jit-able
    ``(x_loc, src_loc, val, dst_local, rows, row_last, rows_loc, send_idx,
    recv_idx, q) -> x_loc`` where ``x_loc`` is the stacked per-shard frontier and
    ``row_update`` is the 4-arg query form.  Each commit step publishes the
    shard's own chunk locally, then all-gathers only the ``(D, H)`` boundary
    entries — O(boundary) wire instead of the replicated O(P·δ).

    With ``feature_dims=1`` the local frontier is ``(D, L, F)`` and each halo
    all-gather ships ``(H, F)`` boundary *blocks* — the FrontierPlan is
    unchanged; only the gathered payload widens.
    """
    axis_size = mesh_axis_sizes(mesh)[axis]
    if axis_size != plan.D:
        raise ValueError(f"plan built for D={plan.D}, mesh axis |{axis}|={axis_size}")
    S, passes = sched.S, sched.passes

    def body(
        x, src_loc, val, dst_local, rows_g, row_last, rows_loc, send_idx, recv_idx, q
    ):
        # Per-shard blocks: x (1, L)+feat; plan blocks (1, S, P_loc, ·);
        # schedule cells (S, P_loc, ·); send (S, 1, H); recv (S, 1, D·H).
        sl, rl = src_loc[0], rows_loc[0]
        feat = x.shape[2:]

        def commit_step(s, xv):
            src_s = jax.lax.dynamic_index_in_dim(sl, s, 0, keepdims=False)
            val_s = jax.lax.dynamic_index_in_dim(val, s, 0, keepdims=False)
            dst_s = jax.lax.dynamic_index_in_dim(dst_local, s, 0, keepdims=False)
            rg_s = jax.lax.dynamic_index_in_dim(rows_g, s, 0, keepdims=False)
            last_s = jax.lax.dynamic_index_in_dim(row_last, s, 0, keepdims=False)
            rl_s = jax.lax.dynamic_index_in_dim(rl, s, 0, keepdims=False)
            snd_s = jax.lax.dynamic_index_in_dim(send_idx, s, 0, keepdims=False)[0]
            rcv_s = jax.lax.dynamic_index_in_dim(recv_idx, s, 0, keepdims=False)[0]

            gathered = xv[src_s]  # (P_loc, M)+feat — owned + halo reads, local
            contrib = edge_products(semiring, gathered, val_s, dst_s)
            reduced = sorted_segment_reduce(semiring, contrib, dst_s, last_s, passes)
            old = xv[rl_s]
            new = row_update(old, reduced, rg_s, q)
            newv = new.reshape((-1,) + feat).astype(xv.dtype)
            # Owner-computes publish: only this shard writes its owned rows.
            xv = xv.at[rl_s.reshape(-1)].set(newv, mode="drop", unique_indices=False)
            # Halo exchange: ship only the boundary entries of this commit.
            buf = jax.lax.all_gather(newv[snd_s], axis, axis=0, tiled=True)
            return xv.at[rcv_s].set(
                buf.astype(xv.dtype), mode="drop", unique_indices=False
            )

        return jax.lax.fori_loop(0, S, commit_step, x[0])[None]

    cell = P(None, axis, None)
    block = P(axis, None, None, None)
    x_spec = P(axis, *((None,) * (1 + feature_dims)))
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, block, cell, cell, cell, cell, block, cell, cell, P()),
        out_specs=x_spec,
        check_vma=False,
    )


def frontier_round_ext_fn(
    sched: DeviceSchedule,
    plan: FrontierPlan,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    feature_dims: int = 0,
) -> Callable:
    """Global-frontier view of the halo round: ``(x_ext, q, *plan args) -> x_ext``.

    Scatters ``x_ext`` into the owner-computes layout, runs one halo round,
    and gathers the owned entries back (the dump slot passes through), so
    host-driven convergence loops and residuals see the familiar
    ``(n + 1,)+feat`` frontier.  Argument order after ``q`` matches
    :func:`frontier_plan_args`.
    """
    rnd = frontier_sharded_round_fn(
        sched, plan, semiring, row_update, mesh, axis, feature_dims
    )

    def fn(
        x_ext,
        q,
        src_loc,
        val,
        dst_local,
        rows_g,
        row_last,
        rows_loc,
        send,
        recv,
        gidx,
        oflat,
    ):
        feat = x_ext.shape[1:]
        x_loc = x_ext[gidx]
        x_out = rnd(
            x_loc, src_loc, val, dst_local, rows_g, row_last, rows_loc, send, recv, q
        )
        owned = x_out.reshape((-1,) + feat)[oflat]
        return jnp.concatenate([owned, x_ext[-1:]])

    return fn


def frontier_ef_init(plan: FrontierPlan, feat: tuple = ()) -> jnp.ndarray:
    """Zero error-feedback residuals ``(D, S, H)+feat`` f32 for the quantized halo.

    One residual per (shard, commit step, boundary row[, feature column]):
    whatever the quantizer could not represent this round is added back to the
    same boundary row's send value next round, so quantization error
    accumulates into the iteration as bounded staleness instead of bias.
    Harmless (all zeros stay zero) when ``halo_dtype="f32"``.  ``feat`` is the
    frontier's trailing feature shape — matrix frontiers quantize per column,
    so they carry per-feature residuals.
    """
    return jnp.zeros((plan.D, plan.S, plan.H) + tuple(feat), jnp.float32)


def frontier_pallas_round_fn(
    sched: DeviceSchedule,
    plan: FrontierPlan,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    halo_dtype: str = "f32",
    interpret: bool | None = None,
    feature_dims: int = 0,
) -> Callable:
    """Fused owner-computes round: one Pallas kernel per commit per shard.

    Returns jit-able
    ``(x_loc, ef, src_loc, val, dst_local, rows, row_last, rows_loc, send_idx,
    recv_idx, q) -> (x_loc, ef)``.  Identical exchange discipline to
    :func:`frontier_sharded_round_fn`, but each shard's commit step —
    gather/⊗/segment-⊕/row-update/publish plus boundary-row selection — runs
    as a single :func:`repro.kernels.round_block.fused_halo_step_fn` kernel
    with the shard's ``(L,)`` frontier slice pinned in VMEM.  Only the
    ``(D, H)`` boundary all-gather (and, quantized, a ``(D,)`` scale gather)
    runs in XLA between kernel invocations; the cross-shard dependency of
    commit ``s`` on commit ``s - 1`` makes that exchange irreducible.

    ``halo_dtype="f32"`` is bit-identical per round to the XLA halo round
    (and hence to every other backend).  ``"int8"`` / ``"fp8"`` quantize each
    shard's send rows against a per-(shard, commit) max-abs scale with
    error-feedback residuals ``ef`` carried across rounds — the all-gathered
    payload is genuinely 1 byte/element on the wire, at the price of
    quantization noise entering the iteration as extra staleness.

    With ``feature_dims=1`` each send is an ``(H, F)`` boundary block and
    quantization applies **per feature column**: the max-abs scale is ``(F,)``
    per (shard, commit) and the error-feedback residuals carry a feature axis,
    so one large column can never wash out another's resolution.
    """
    axis_size = mesh_axis_sizes(mesh)[axis]
    if axis_size != plan.D:
        raise ValueError(f"plan built for D={plan.D}, mesh axis |{axis}|={axis_size}")
    resolve_halo_dtype(halo_dtype, semiring)
    qinfo = _HALO_QUANT.get(halo_dtype)
    S, H = sched.S, plan.H
    step = fused_halo_step_fn(
        semiring,
        row_update,
        P_loc=plan.P_loc,
        M=sched.M,
        delta=sched.delta,
        L=plan.L,
        H=H,
        passes=sched.passes,
        interpret=interpret,
    )

    def body(
        x,
        ef,
        src_loc,
        val,
        dst_local,
        rows_g,
        row_last,
        rows_loc,
        send_idx,
        recv_idx,
        q,
    ):
        # Per-shard blocks: x (1, L); ef (1, S, H); plan blocks
        # (1, S, P_loc, ·); schedule cells (S, P_loc, ·); send (S, 1, H);
        # recv (S, 1, D·H).
        sl, rl = src_loc[0], rows_loc[0]

        def commit_step(s, carry):
            xv, efv = carry
            src_s = jax.lax.dynamic_index_in_dim(sl, s, 0, keepdims=False)
            val_s = jax.lax.dynamic_index_in_dim(val, s, 0, keepdims=False)
            dst_s = jax.lax.dynamic_index_in_dim(dst_local, s, 0, keepdims=False)
            rg_s = jax.lax.dynamic_index_in_dim(rows_g, s, 0, keepdims=False)
            last_s = jax.lax.dynamic_index_in_dim(row_last, s, 0, keepdims=False)
            rl_s = jax.lax.dynamic_index_in_dim(rl, s, 0, keepdims=False)
            snd_s = jax.lax.dynamic_index_in_dim(send_idx, s, 0, keepdims=False)[0]
            rcv_s = jax.lax.dynamic_index_in_dim(recv_idx, s, 0, keepdims=False)[0]

            # Fused commit: publish locally, select boundary rows, in-place
            # on the VMEM-resident frontier slice.
            xv, send = step(xv, src_s, val_s, dst_s, last_s, rg_s, rl_s, snd_s, q)

            if qinfo is None:
                buf = jax.lax.all_gather(send, axis, axis=0, tiled=True)
                xv = xv.at[rcv_s].set(
                    buf.astype(xv.dtype), mode="drop", unique_indices=False
                )
                return xv, efv

            qdtype, qmax = qinfo
            ef_s = jax.lax.dynamic_index_in_dim(efv, s, 0, keepdims=False)
            want = send.astype(jnp.float32) + ef_s  # (H,)+feat
            # Per-feature max-abs scale: () for vectors, (F,) for matrices.
            scale = jnp.maximum(jnp.max(jnp.abs(want), axis=0), 1e-30) / qmax
            if qdtype == jnp.int8:
                qv = jnp.clip(jnp.round(want / scale), -qmax, qmax).astype(qdtype)
            else:
                qv = jnp.clip(want / scale, -qmax, qmax).astype(qdtype)
            # 1-byte elements on the wire; scales are a (D,)+feat f32 side
            # channel.
            qbuf = jax.lax.all_gather(qv, axis, axis=0, tiled=True)
            sbuf = jax.lax.all_gather(scale[None], axis, axis=0, tiled=True)
            deq = qbuf.astype(jnp.float32) * jnp.repeat(sbuf, H, axis=0)
            efv = jax.lax.dynamic_update_index_in_dim(
                efv, want - qv.astype(jnp.float32) * scale, s, 0
            )
            xv = xv.at[rcv_s].set(
                deq.astype(xv.dtype), mode="drop", unique_indices=False
            )
            return xv, efv

        xv, efv = jax.lax.fori_loop(0, S, commit_step, (x[0], ef[0]))
        return xv[None], efv[None]

    cell = P(None, axis, None)
    block = P(axis, None, None, None)
    x_spec = P(axis, *((None,) * (1 + feature_dims)))
    ef_spec = P(axis, *((None,) * (2 + feature_dims)))
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec,
            ef_spec,
            block,
            cell,
            cell,
            cell,
            cell,
            block,
            cell,
            cell,
            P(),
        ),
        out_specs=(x_spec, ef_spec),
        check_vma=False,
    )


def frontier_pallas_round_ext_fn(
    sched: DeviceSchedule,
    plan: FrontierPlan,
    semiring: Semiring,
    row_update,
    mesh,
    axis: str = "data",
    halo_dtype: str = "f32",
    interpret: bool | None = None,
    feature_dims: int = 0,
) -> Callable:
    """Global-frontier view of the fused halo round.

    ``(x_ext, ef, q, *plan args) -> (x_ext, ef)`` — same scatter/gather
    framing as :func:`frontier_round_ext_fn` (argument order after ``q``
    matches :func:`frontier_plan_args`), with the error-feedback residuals
    threaded through so callers carry them across rounds.
    """
    rnd = frontier_pallas_round_fn(
        sched,
        plan,
        semiring,
        row_update,
        mesh,
        axis,
        halo_dtype,
        interpret,
        feature_dims,
    )

    def fn(
        x_ext,
        ef,
        q,
        src_loc,
        val,
        dst_local,
        rows_g,
        row_last,
        rows_loc,
        send,
        recv,
        gidx,
        oflat,
    ):
        feat = x_ext.shape[1:]
        x_loc = x_ext[gidx]
        x_out, ef_out = rnd(
            x_loc, ef, src_loc, val, dst_local, rows_g, row_last, rows_loc, send,
            recv, q,
        )
        owned = x_out.reshape((-1,) + feat)[oflat]
        return jnp.concatenate([owned, x_ext[-1:]]), ef_out

    return fn


def frontier_plan_args(sched: DeviceSchedule, plan: FrontierPlan) -> tuple:
    """The runtime argument tuple for :func:`frontier_round_ext_fn`."""
    return (
        plan.src_loc,
        sched.val,
        sched.dst_local,
        sched.rows,
        sched.row_last,
        plan.rows_loc,
        plan.send_idx,
        plan.recv_idx,
        plan.gather_index,
        plan.owned_flat,
    )
