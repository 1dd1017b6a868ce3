"""Transformer building blocks: attention layer, dense MLP, MoE MLP.

Every block is a pair ``init_*(key, cfg) -> params`` / ``apply_*(params, x,
...) -> y`` over plain dicts of jnp arrays, so parameter trees stack cleanly
along a leading layer axis for ``lax.scan`` and shard with PartitionSpecs
resolved by name (repro.dist.sharding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.compat import current_mesh
from repro.dist.sharding import logical
from repro.models.config import ModelConfig
from repro.models.layers import (
    F32,
    act_fn,
    apply_rope,
    decode_attention,
    dense_init,
    flash_attention,
    rms_norm,
    split_keys,
)

# --------------------------------------------------------------------------- #
# Attention layer (self-attention + MLP), llama-style pre-norm
# --------------------------------------------------------------------------- #


def init_attn_layer(key, cfg: ModelConfig, dtype):
    d, hd, Hq, Hkv, ff = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv, cfg.d_ff
    ks = split_keys(key, 8)
    p = {
        "ln1": jnp.ones((d,), dtype),
        "wq": dense_init(ks[0], (d, Hq * hd), dtype),
        "wk": dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (Hq * hd, d), dtype),
        "ln2": jnp.ones((d,), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if cfg.family == "moe":
        p["moe"] = init_moe_mlp(ks[4], cfg, dtype)
    else:
        p["mlp"] = init_dense_mlp(ks[4], d, ff, dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, angles):
    B, S, d = x.shape
    hd, Hq, Hkv = cfg.hd, cfg.n_heads, cfg.n_kv
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    # Megatron-SP boundary: re-gather the sequence here so the projections
    # run (tokens_full × d) × (d × out_shard) — weight grads then reduce
    # *sharded* instead of as full-matrix all-reduces (§Perf).
    h = logical(h, ("batch", None, "embed"))
    q = (h @ p["wq"]).reshape(B, S, Hq, hd)
    k = (h @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (h @ p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    return q, k, v


def apply_attn_layer(p, cfg: ModelConfig, x, angles, *, window=0, causal=True):
    """Training / prefill path (no cache). Returns (y, (k, v)) for caching."""
    B, S, d = x.shape
    q, k, v = _qkv(p, cfg, x, angles)
    o = flash_attention(
        q,
        k,
        v,
        causal=causal,
        q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk,
        window=window,
        schedule=cfg.attn_schedule,
    )
    o = logical(o.reshape(B, S, -1) @ p["wo"], ("batch", "seq", "embed"))
    x = x + o  # reduce-scatter back to the SP layout
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = apply_moe_mlp(p["moe"], cfg, h)
    else:
        h = logical(h, ("batch", None, "embed"))  # SP boundary (MLP)
        y = logical(apply_dense_mlp(p["mlp"], cfg, h), ("batch", "seq", "embed"))
        aux = jnp.zeros((), F32)
    return x + y, (k, v), aux


def _quant_i8(x):
    """x (..., hd) → (int8, f32 scale over hd)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(F32)), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(F32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def apply_attn_layer_decode(
    p, cfg: ModelConfig, x, angles, cache, cur_len, *, window=0
):
    """Decode path: x (B,1,d); cache = (k_cache, v_cache) (B,S,Hkv,hd) or the
    int8-quantized 4-tuple (k_i8, v_i8, k_scale, v_scale)."""
    B, _, d = x.shape
    q, k_new, v_new = _qkv(p, cfg, x, angles)
    quant = cfg.kv_quant_int8 and len(cache) == 4
    if quant:
        k_cache, v_cache, k_sc, v_sc = cache
    else:
        k_cache, v_cache = cache
    mesh = _current_mesh_info()
    S = k_cache.shape[1]
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and cfg.n_kv % mesh.shape["model"] != 0  # cache is seq-sharded
        and S % mesh.shape["model"] == 0
        and not window
    ):
        # §Perf: sequence-parallel decode — local cache write + partial
        # softmax, psum-combined (replaces cache-sized all-gathers).
        from repro.models.layers import seq_parallel_decode_attention

        scales = (k_sc, v_sc) if quant else None
        o, new_cache = seq_parallel_decode_attention(
            q, k_cache, v_cache, k_new, v_new, cur_len, mesh, scales=scales
        )
        x = x + o.reshape(B, 1, -1) @ p["wo"]
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = apply_moe_mlp(p["moe"], cfg, h)
        else:
            y = apply_dense_mlp(p["mlp"], cfg, h)
        return x + y, new_cache
    # write the new kv at cur_len (per-batch dynamic index)
    idx = cur_len  # (B,)
    bidx = jnp.arange(B)
    if quant:
        kq, ks = _quant_i8(k_new[:, 0])
        vq, vs = _quant_i8(v_new[:, 0])
        k_cache = k_cache.at[bidx, idx].set(kq)
        v_cache = v_cache.at[bidx, idx].set(vq)
        k_sc = k_sc.at[bidx, idx].set(ks)
        v_sc = v_sc.at[bidx, idx].set(vs)
        k_deq = (k_cache.astype(F32) * k_sc[..., None]).astype(k_new.dtype)
        v_deq = (v_cache.astype(F32) * v_sc[..., None]).astype(v_new.dtype)
        o = decode_attention(q, k_deq, v_deq, (cur_len + 1)[:, None], window=window)
        new_cache = (k_cache, v_cache, k_sc, v_sc)
    else:
        k_cache = k_cache.at[bidx, idx].set(k_new[:, 0])
        v_cache = v_cache.at[bidx, idx].set(v_new[:, 0])
        o = decode_attention(q, k_cache, v_cache, (cur_len + 1)[:, None], window=window)
        new_cache = (k_cache, v_cache)
    x = x + o.reshape(B, 1, -1) @ p["wo"]
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, _ = apply_moe_mlp(p["moe"], cfg, h)
    else:
        y = apply_dense_mlp(p["mlp"], cfg, h)
    return x + y, new_cache


# --------------------------------------------------------------------------- #
# Dense (SwiGLU / GeLU) MLP
# --------------------------------------------------------------------------- #


def init_dense_mlp(key, d, ff, dtype):
    ks = split_keys(key, 3)
    return {
        "wg": dense_init(ks[0], (d, ff), dtype),
        "wu": dense_init(ks[1], (d, ff), dtype),
        "wd": dense_init(ks[2], (ff, d), dtype),
    }


def apply_dense_mlp(p, cfg: ModelConfig, h):
    a = act_fn(cfg.act)
    return (a(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


# --------------------------------------------------------------------------- #
# MoE MLP: top-k routing, sort-based capacity dispatch (dropping), EP-ready
# --------------------------------------------------------------------------- #


def init_moe_mlp(key, cfg: ModelConfig, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = split_keys(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), F32, scale=0.02),
        "wg": dense_init(ks[1], (E, d, ff), dtype),
        "wu": dense_init(ks[2], (E, d, ff), dtype),
        "wd": dense_init(ks[3], (E, ff, d), dtype),
    }


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(np.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # multiple of 8 lanes


def _current_mesh_info():
    return current_mesh()


def apply_moe_mlp(p, cfg: ModelConfig, x):
    """MoE layer dispatcher: shard_map EP when a mesh with a "model" axis is
    active (production path, explicit all-to-alls), local sort-based capacity
    dispatch otherwise (single-device smoke tests)."""
    mesh = _current_mesh_info()
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and cfg.n_experts % mesh.shape["model"] == 0
        and mesh.shape["model"] > 1
    ):
        return _moe_shardmap(p, cfg, x, mesh)
    return _moe_local(p, cfg, x)


def _moe_local(p, cfg: ModelConfig, x):
    """x (B,S,d) → (y, load_balance_loss).  Sort-based capacity dispatch:

    tokens are argsorted by expert id and packed into an (E, C+1, d) buffer
    (slot C = overflow drop), experts run as one batched einsum (grouped
    GEMM), and results scatter back weighted by the top-k gates.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    N = B * S
    x2 = x.reshape(N, d)
    a = act_fn(cfg.act)

    logits = (x2.astype(F32) @ p["router"]).astype(F32)  # (N, E)
    gates_full = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates_full, k)  # (N, k)
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # load-balance loss (Switch/GShard style)
    counts = jnp.zeros((E,), F32).at[topi.reshape(-1)].add(1.0)
    frac_tokens = counts / (N * k)
    frac_prob = gates_full.mean(0)
    lb_loss = E * jnp.sum(frac_tokens * frac_prob)

    flat_e = topi.reshape(-1)  # (N*k,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos_in_e = jnp.arange(N * k) - seg_start[sorted_e]
    C = moe_capacity(cfg, N)
    slot = jnp.minimum(pos_in_e, C)  # C = overflow slot
    token_of = order // k

    buf = jnp.zeros((E, C + 1, d), x.dtype)
    buf = buf.at[sorted_e, slot].set(x2[token_of])
    h = jnp.einsum("ecd,edf->ecf", buf, p["wg"], preferred_element_type=F32)
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"], preferred_element_type=F32)
    hexp = (a(h) * u).astype(x.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", hexp, p["wd"], preferred_element_type=F32)

    vals = out_buf[sorted_e, slot]  # (N*k, d)
    w = gates.reshape(-1)[order] * (pos_in_e < C)
    vals = vals * w[:, None]
    y = jax.ops.segment_sum(vals, token_of, num_segments=N)
    return y.reshape(B, S, d).astype(x.dtype), lb_loss


# ---- shard_map expert parallelism ----------------------------------------- #


def _pack_by_group(ids, n_groups: int, capacity: int):
    """Sort items by group id; returns (order, group, slot, keep).

    ``slot`` is each item's position within its group, clipped to
    ``capacity`` (the drop slot).
    """
    order = jnp.argsort(ids)
    sorted_g = ids[order]
    seg_start = jnp.searchsorted(sorted_g, jnp.arange(n_groups))
    pos = jnp.arange(ids.shape[0]) - seg_start[jnp.clip(sorted_g, 0, n_groups - 1)]
    keep = (pos < capacity) & (sorted_g < n_groups)
    slot = jnp.where(keep, pos, capacity)
    return order, sorted_g, slot, keep


def _expert_ffn(p_loc, cfg, buf):
    """buf (E_loc, C, d) → (E_loc, C, d) through the gated MLP."""
    a = act_fn(cfg.act)
    h = jnp.einsum("ecd,edf->ecf", buf, p_loc["wg"], preferred_element_type=F32)
    u = jnp.einsum("ecd,edf->ecf", buf, p_loc["wu"], preferred_element_type=F32)
    hexp = (a(h) * u).astype(buf.dtype)
    return jnp.einsum("ecf,efd->ecd", hexp, p_loc["wd"], preferred_element_type=F32)


def _moe_shardmap(p, cfg: ModelConfig, x, mesh):
    """Expert parallelism with explicit collectives (the production path).

    Experts are sharded over "model" (E_loc per rank); expert weights are
    additionally FSDP-sharded over "data" and all-gathered per layer (the
    gather's transpose is the grad reduce-scatter).  Two schedules:

    * seq divisible by the model axis (train/prefill): tokens are SP-sharded;
      assignments are packed per target rank and exchanged with
      ``all_to_all``, computed by the owning rank, and returned by the
      inverse ``all_to_all`` (MoE dispatch/combine exactly as deployed).
    * otherwise (decode, S == 1): tokens are replicated over "model"; each
      rank computes only its own experts' assignments and the partial sums
      are ``psum``-ed — no all_to_all on the hot decode path.
    """
    B, S, d = x.shape
    E, k, M = cfg.n_experts, cfg.top_k, mesh.shape["model"]
    E_loc = E // M
    axes = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    fsdp_ok = "data" in axes
    a2a_path = S % M == 0 and (B % max(np.prod([mesh.shape[a] for a in dp]), 1) == 0)
    P_ = jax.sharding.PartitionSpec

    def gather_w(w, axis):
        return jax.lax.all_gather(w, "data", axis=axis, tiled=True) if fsdp_ok else w

    def body(x_loc, router, wg, wu, wd):
        p_loc = {
            "wg": gather_w(wg, 1).astype(x_loc.dtype),
            "wu": gather_w(wu, 1).astype(x_loc.dtype),
            "wd": gather_w(wd, 2).astype(x_loc.dtype),
        }
        b, s, _ = x_loc.shape
        N = b * s
        x2 = x_loc.reshape(N, d)
        logits = x2.astype(F32) @ router
        gates_full = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(gates_full, k)  # (N, k)
        gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

        counts = jnp.zeros((E,), F32).at[topi.reshape(-1)].add(1.0)
        lb = E * jnp.sum(counts / (N * k) * gates_full.mean(0))
        lb = jax.lax.pmean(lb, tuple(a for a in ("pod", "data", "model") if a in axes))

        flat_e = topi.reshape(-1)  # (N·k,) global expert ids
        flat_g = gates.reshape(-1)

        if a2a_path:
            # ---- pack per destination rank and exchange ------------------ #
            C_send = max(8, -(-int(np.ceil(N * k * cfg.capacity_factor / M)) // 8) * 8)
            rank_of = flat_e // E_loc
            order, _, slot, keep = _pack_by_group(rank_of, M, C_send)
            token_of = order // k
            send = jnp.zeros((M, C_send + 1, d), x_loc.dtype)
            send = send.at[rank_of[order], slot].set(x2[token_of] * keep[:, None])
            send_eid = jnp.full((M, C_send + 1), E_loc, jnp.int32)
            send_eid = send_eid.at[rank_of[order], slot].set(
                jnp.where(keep, flat_e[order] % E_loc, E_loc).astype(jnp.int32)
            )
            recv = jax.lax.all_to_all(
                send[:, :C_send], "model", split_axis=0, concat_axis=0, tiled=True
            )  # (M, C_send, d) — what every rank sent to me
            recv_eid = jax.lax.all_to_all(
                send_eid[:, :C_send], "model", split_axis=0, concat_axis=0, tiled=True
            )
            # ---- local grouped GEMM over my experts ---------------------- #
            R = M * C_send
            r2 = recv.reshape(R, d)
            eid = recv_eid.reshape(R)
            C_e = max(8, -(-int(np.ceil(R * 1.0 / E_loc)) // 8) * 8)
            order2, _, slot2, keep2 = _pack_by_group(eid, E_loc, C_e)
            buf = jnp.zeros((E_loc, C_e + 1, d), x_loc.dtype)
            buf = buf.at[eid[order2].clip(0, E_loc - 1) * keep2, slot2].set(
                r2[order2] * keep2[:, None]
            )
            out_buf = _expert_ffn(p_loc, cfg, buf[:, :C_e]).astype(x_loc.dtype)
            out_r = jnp.zeros((R, d), x_loc.dtype)
            out_r = out_r.at[order2].set(
                out_buf[
                    eid[order2].clip(0, E_loc - 1) * keep2, jnp.minimum(slot2, C_e - 1)
                ]
                * keep2[:, None]
            )
            back = jax.lax.all_to_all(
                out_r.reshape(M, C_send, d), "model", split_axis=0, concat_axis=0,
                tiled=True,
            )
            # ---- combine ------------------------------------------------- #
            vals = jnp.zeros((N * k, d), x_loc.dtype)
            vals = vals.at[order].set(
                back[rank_of[order], jnp.minimum(slot, C_send - 1)] * keep[:, None]
            )
            y = jax.ops.segment_sum(
                vals * flat_g[:, None].astype(x_loc.dtype), jnp.arange(N * k) // k, N
            )
        else:
            # ---- replicated tokens; my experts only; psum over model ----- #
            my_rank = jax.lax.axis_index("model")
            local = (flat_e // E_loc) == my_rank
            eid = jnp.where(local, flat_e % E_loc, E_loc).astype(jnp.int32)
            C_e = max(
                8,
                -(-int(np.ceil(N * k * cfg.capacity_factor / max(E, 1) * E_loc)) // 8)
                * 8,
            )
            order2, _, slot2, keep2 = _pack_by_group(eid, E_loc, C_e)
            token_of2 = order2 // k
            buf = jnp.zeros((E_loc, C_e + 1, d), x_loc.dtype)
            buf = buf.at[eid[order2].clip(0, E_loc - 1) * keep2, slot2].set(
                x2[token_of2] * keep2[:, None]
            )
            out_buf = _expert_ffn(p_loc, cfg, buf[:, :C_e]).astype(x_loc.dtype)
            vals = out_buf[
                eid[order2].clip(0, E_loc - 1), jnp.minimum(slot2, C_e - 1)
            ] * keep2[:, None]
            y = jax.ops.segment_sum(
                vals * flat_g[order2][:, None].astype(x_loc.dtype), token_of2, N
            )
            y = jax.lax.psum(y, "model")
        return y.reshape(b, s, d), lb

    seq_spec = "model" if a2a_path else None
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P_(dp if dp else None, seq_spec, None),  # x
            P_(None, None),  # router
            P_("model", "data" if fsdp_ok else None, None),  # wg
            P_("model", "data" if fsdp_ok else None, None),  # wu
            P_("model", None, "data" if fsdp_ok else None),  # wd
        ),
        out_specs=(P_(dp if dp else None, seq_spec, None), P_()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
