"""Shared neural-net layers (pure JAX, TPU-shaped).

Attention is implemented as a chunked, numerically-stable streaming softmax
(flash-attention schedule) in pure JAX: the dry-run must lower on the CPU
backend where ``pallas_call`` is unavailable outside interpret mode, so the
kernel-level tiling is expressed with ``lax.scan`` over (q-chunk × kv-chunk)
tiles — the same VMEM-sized working set a Pallas flash kernel would use
(DESIGN.md §8).  Two causal schedules are provided:

* ``masked`` — every q-chunk visits every kv-chunk with a mask (baseline;
  2× FLOP waste on causal).
* ``banded`` — q-chunk ``i`` visits kv-chunks ``0..i`` only, via a
  lower-triangular gather of tile coordinates (the §Perf compute-term fix).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


F32 = jnp.float32

# --------------------------------------------------------------------------- #
# Norms / activations
# --------------------------------------------------------------------------- #


def rms_norm(x, w, eps=1e-5):
    h = x.astype(F32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * w.astype(F32)).astype(x.dtype)


def layer_norm(x, w, b, eps=1e-5):
    h = x.astype(F32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    return ((h - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def act_fn(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# --------------------------------------------------------------------------- #
# RoPE / M-RoPE
# --------------------------------------------------------------------------- #


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))  # (hd/2,)


def rope_angles(positions, head_dim, theta, mrope_sections=()):
    """Angles (…, S, hd/2) from positions.

    ``positions``: (B, S) int32 for standard RoPE, or (B, 3, S) for M-RoPE
    (temporal / height / width streams — Qwen2-VL §3).  With M-RoPE the
    hd/2 frequency slots are split into ``mrope_sections`` groups, each
    driven by its own position stream.
    """
    freqs = jnp.asarray(rope_freqs(head_dim, theta), dtype=F32)
    if not mrope_sections:
        return positions[..., None].astype(F32) * freqs  # (B, S, hd/2)
    sections = np.asarray(mrope_sections)
    assert sections.sum() == head_dim // 2
    stream_of_freq = np.repeat(np.arange(len(sections)), sections)  # (hd/2,)
    # positions (B, 3, S) → per-freq stream positions (B, S, hd/2)
    pos = positions.astype(F32)[:, stream_of_freq, :]  # (B, hd/2, S)
    pos = jnp.swapaxes(pos, 1, 2)  # (B, S, hd/2)
    return pos * freqs


def apply_rope(x, angles):
    """x: (B, S, H, hd); angles: (B, S, hd/2). Rotate-half convention."""
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Chunked flash-style attention (training / prefill)
# --------------------------------------------------------------------------- #


def _attend_tile(q, k, v, mask, scale):
    """One (qc × kc) tile. q:(B,qc,Hkv,G,D) k:(B,kc,Hkv,D) v:(B,kc,Hkv,D).

    Returns (scores_max, exp_sum, weighted_v) in f32 for streaming combine.
    """
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=F32)
    logits = logits * scale
    logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)  # (B,H,G,q)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B,H,G,q)
    pv = jnp.einsum(
        "bhgqk,bkhd->bhgqd", p.astype(v.dtype), v, preferred_element_type=F32
    )
    return m, l, pv


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    window: int = 0,
    schedule: str = "banded",
    q_offset: int = 0,
):
    """Streaming-softmax attention.  q:(B,Sq,Hq,D), k/v:(B,Skv,Hkv,D).

    GQA via reshape of q-heads into (Hkv, G).  ``window`` > 0 restricts to a
    local causal band (recurrentgemma).  ``q_offset`` is the absolute position
    of q[0] (prefill continuation).  Output (B,Sq,Hq,D) in q.dtype.
    """
    B, Sq0, Hq, D = q.shape
    _, Skv0, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / np.sqrt(D)
    q_chunk = min(q_chunk, Sq0)
    kv_chunk = min(kv_chunk, Skv0)
    # pad to chunk multiples; padded keys are masked out, padded q rows sliced
    Sq = -(-Sq0 // q_chunk) * q_chunk
    Skv = -(-Skv0 // kv_chunk) * kv_chunk
    if Sq != Sq0:
        q = jnp.pad(q, ((0, 0), (0, Sq - Sq0), (0, 0), (0, 0)))
    if Skv != Skv0:
        k = jnp.pad(k, ((0, 0), (0, Skv - Skv0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv - Skv0), (0, 0), (0, 0)))
    nq, nk = Sq // q_chunk, Skv // kv_chunk

    qr = q.reshape(B, nq, q_chunk, Hkv, G, D)
    kr = k.reshape(B, nk, kv_chunk, Hkv, D)
    vr = v.reshape(B, nk, kv_chunk, Hkv, D)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(Skv).reshape(nk, kv_chunk)

    def tile_mask(qi, ki):
        qp = q_pos[qi][:, None]  # (qc, 1)
        kp = k_pos[ki][None, :]  # (1, kc)
        m = kp < Skv0  # mask kv padding
        if causal:
            m &= kp <= qp
        if window:
            m &= kp > qp - window
        return m  # (qc, kc)

    def combine(carry, tile):
        m_prev, l_prev, acc = carry
        m_t, l_t, pv_t = tile
        m_new = jnp.maximum(m_prev, m_t)
        a = jnp.exp(m_prev - m_new)
        b = jnp.exp(m_t - m_new)
        l_new = l_prev * a + l_t * b
        acc = acc * a[..., None] + pv_t * b[..., None]
        return m_new, l_new, acc

    @jax.checkpoint  # flash-style backward: recompute tiles, save only q/k/v
    def one_q_chunk(qi):
        qc = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)  # (B,qc,Hkv,G,D)

        if schedule == "banded" and causal:
            # kv chunks strictly above the diagonal are fully masked; visit
            # only 0..diag (and, with a window, only the band).  The loop
            # length is static (= nk); skipped tiles cost a predicated copy.
            def kv_step(carry, ki):
                def visit(carry):
                    kc = jax.lax.dynamic_index_in_dim(kr, ki, 1, keepdims=False)
                    vc = jax.lax.dynamic_index_in_dim(vr, ki, 1, keepdims=False)
                    tile = _attend_tile(
                        qc, kc, vc, tile_mask(qi, ki)[None, None, None], scale
                    )
                    return combine(carry, tile)

                # live iff this tile intersects the causal band
                first_k = k_pos[ki][0]
                last_k = k_pos[ki][-1]
                lo = q_pos[qi][0] - (window - 1) if window else -1
                live = (last_k >= lo) & (first_k <= q_pos[qi][-1])
                return jax.lax.cond(live, visit, lambda c: c, carry), None

            init = (
                jnp.full((B, Hkv, G, q_chunk), -jnp.inf, F32),
                jnp.zeros((B, Hkv, G, q_chunk), F32),
                jnp.zeros((B, Hkv, G, q_chunk, D), F32),
            )
            (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        else:

            def kv_step(carry, ki):
                kc = jax.lax.dynamic_index_in_dim(kr, ki, 1, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(vr, ki, 1, keepdims=False)
                tile = _attend_tile(
                    qc, kc, vc, tile_mask(qi, ki)[None, None, None], scale
                )
                return combine(carry, tile), None

            init = (
                jnp.full((B, Hkv, G, q_chunk), -jnp.inf, F32),
                jnp.zeros((B, Hkv, G, q_chunk), F32),
                jnp.zeros((B, Hkv, G, q_chunk, D), F32),
            )
            (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))

        out = acc / jnp.maximum(l[..., None], 1e-30)  # (B,H,G,q,D)
        return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)  # (B,q,Hkv,G,D)

    out = jax.lax.map(one_q_chunk, jnp.arange(nq))  # (nq,B,qc,Hkv,G,D)
    out = jnp.transpose(out, (1, 0, 2, 3, 4, 5)).reshape(B, Sq, Hq, D)
    return out[:, :Sq0]


# --------------------------------------------------------------------------- #
# Decode attention (single query step against a cache)
# --------------------------------------------------------------------------- #


def seq_parallel_decode_attention(
    q, k_cache, v_cache, k_new, v_new, cur_len, mesh, scales=None
):
    """Sequence-parallel decode attention + cache update (shard_map).

    The §Perf fix for collective-bound decode: with the KV cache sharded over
    "model" on the *sequence* dim, the naive pjit lowering all-gathers the
    cache both for the dynamic cache update and for the softmax.  Here every
    shard (a) writes the new K/V locally iff ``cur_len`` lands in its range,
    and (b) computes flash-style partial (max, sum, weighted-V) over its seq
    slice; the cross-shard combine is two psums of (B,H)-sized tensors —
    KBs instead of the cache's GBs.

    q (B,1,Hq,D); caches (B,S,Hkv,D) sharded P(dp, "model", None, None);
    k_new/v_new (B,1,Hkv,D) replicated over "model"; cur_len (B,).
    With ``scales=(k_scale, v_scale)`` the caches are int8 and dequantised
    per shard (§Perf: halves the compulsory cache read traffic).
    Returns (out (B,1,Hq,D), new_cache_tuple).
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    axes = set(mesh.axis_names)
    dp = tuple(a for a in ("pod", "data") if a in axes)
    Ps = jax.sharding.PartitionSpec
    att_scale = 1.0 / np.sqrt(D)
    quant = scales is not None

    def _q_i8(x):
        s = jnp.maximum(jnp.max(jnp.abs(x.astype(F32)), axis=-1), 1e-8) / 127.0
        return (
            jnp.clip(jnp.round(x.astype(F32) / s[..., None]), -127, 127).astype(
                jnp.int8
            ),
            s,
        )

    def body(q, kc, vc, kn, vn, cur, *sc):
        b_loc, s_loc = kc.shape[0], kc.shape[1]  # local shapes
        rank = jax.lax.axis_index("model")
        lo = rank * s_loc
        # (a) local cache write: slot = cur - lo when 0 ≤ slot < s_loc
        slot = cur - lo  # (b_loc,)
        bidx = jnp.arange(b_loc)
        in_range = (slot >= 0) & (slot < s_loc)
        safe = jnp.clip(slot, 0, s_loc - 1)
        if quant:
            ksc, vsc = sc
            knq, kns = _q_i8(kn[:, 0])
            vnq, vns = _q_i8(vn[:, 0])
            kc = kc.at[bidx, safe].set(
                jnp.where(in_range[:, None, None], knq, kc[bidx, safe])
            )
            vc = vc.at[bidx, safe].set(
                jnp.where(in_range[:, None, None], vnq, vc[bidx, safe])
            )
            ksc = ksc.at[bidx, safe].set(
                jnp.where(in_range[:, None], kns, ksc[bidx, safe])
            )
            vsc = vsc.at[bidx, safe].set(
                jnp.where(in_range[:, None], vns, vsc[bidx, safe])
            )
            k_use = kc.astype(F32) * ksc[..., None]
            v_use = vc.astype(F32) * vsc[..., None]
        else:
            kc = kc.at[bidx, safe].set(
                jnp.where(in_range[:, None, None], kn[:, 0], kc[bidx, safe])
            )
            vc = vc.at[bidx, safe].set(
                jnp.where(in_range[:, None, None], vn[:, 0], vc[bidx, safe])
            )
            k_use, v_use = kc, vc
        # (b) partial flash over my seq slice
        qr = q.reshape(b_loc, Hkv, G, D)
        logits = jnp.einsum("bhgd,bshd->bhgs", qr, k_use, preferred_element_type=F32)
        logits = logits * att_scale
        pos = lo + jnp.arange(s_loc)
        mask = pos[None, :] <= cur[:, None]  # keys 0..cur (incl. new token)
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
        m_loc = jnp.max(logits, axis=-1)  # (b,Hkv,G)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(logits - m_glob[..., None])
        l_loc = jnp.sum(p, axis=-1)
        pv_loc = jnp.einsum(
            "bhgs,bshd->bhgd", p.astype(q.dtype), v_use.astype(q.dtype),
            preferred_element_type=F32,
        )
        l = jax.lax.psum(l_loc, "model")  # (b,Hkv,G)   — KBs
        pv = jax.lax.psum(pv_loc, "model")  # (b,Hkv,G,D) — KBs
        out = (pv / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        if quant:
            return out.reshape(b_loc, 1, Hq, D), kc, vc, ksc, vsc
        return out.reshape(b_loc, 1, Hq, D), kc, vc

    cache_spec = Ps(dp, "model", None, None)
    sc_spec = Ps(dp, "model", None)
    in_specs = [
        Ps(dp, None, None, None),  # q
        cache_spec,
        cache_spec,
        Ps(dp, None, None, None),  # k_new
        Ps(dp, None, None, None),  # v_new
        Ps(dp),  # cur_len
    ]
    out_specs = [Ps(dp, None, None, None), cache_spec, cache_spec]
    args = [q, k_cache, v_cache, k_new, v_new, cur_len]
    if quant:
        in_specs += [sc_spec, sc_spec]
        out_specs += [sc_spec, sc_spec]
        args += list(scales)
    res = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)
    return res[0], tuple(res[1:])


def decode_attention(q, k_cache, v_cache, cur_len, *, window: int = 0):
    """q:(B,1,Hq,D); caches:(B,S,Hkv,D); attends keys < cur_len.

    Plain einsum with f32 softmax — the (B,H,S) logits tensor is the sharded
    object the decode roofline tracks (KV cache sharded over seq → partial
    softmax all-reduce, DESIGN.md §7).
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, D)
    logits = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache, preferred_element_type=F32)
    logits = logits / np.sqrt(D)
    pos = jnp.arange(S)
    mask = pos[None, :] < cur_len  # (B, S) — cur_len (B,1) or scalar
    if window:
        mask &= pos[None, :] >= cur_len - window
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=F32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Parameter init helpers
# --------------------------------------------------------------------------- #


def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))
