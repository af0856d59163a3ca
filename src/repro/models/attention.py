"""Attention variants: GQA (+ sliding window), MLA (DeepSeek-V2/MiniCPM3 style),
cross-attention, and KV-cache decode paths.

Design notes (TPU adaptation):

* The full-sequence path uses **chunked online-softmax attention** — a
  ``lax.scan`` over KV blocks carrying (max, denom, acc). This bounds the
  materialized score tensor to ``(B, S_q, H, block_k)`` instead of
  ``(B, S_q, H, S_k)``, which is what makes the 32k-prefill dry-run fit in
  HBM. The Pallas kernel in ``repro/kernels/flash_attention.py`` is the fused
  single-kernel twin of this algorithm; this XLA version is the reference /
  dry-run path (the container is CPU-only).
* MLA caches the **compressed** latent (c_kv ‖ k_rope) —`kv_lora + rope_dim`
  floats per token regardless of head count. Two decode paths are provided:
  ``naive`` (reconstruct per-head K/V from the latent each step — the
  faithful-to-published-description baseline) and ``absorb`` (fold W_uk into
  the query and W_uv into the output so attention runs in latent space).
  The absorb path is a §Perf hillclimb subject.
* Sliding-window decode uses a ring-buffer cache of ``window`` slots so the
  ``long_500k`` shape has O(window), not O(S), memory.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.constraints import constrain, mesh_axis_size
from repro.models.common import (
    apply_rope,
    dtype_of,
    init_linear,
    init_rmsnorm,
    linear,
    rmsnorm,
    rope_for,
    softmax_scale_for,
    split_keys,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (full-sequence path)
# ---------------------------------------------------------------------------


def _block_mask(q_pos, k_pos, Sk, causal, window):
    mask = k_pos[None, :] < Sk  # mask padding
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    return mask


def _flash_fwd(q, k, v, causal, window, q_offset, block_k, scale, Sk):
    """Forward online-softmax block scan. q pre-scaled, K/V pre-padded.

    q: (B, Sq, Hkv, G, D); k/v: (nb, B, block, Hkv, D[v]).
    Returns (out (B,Sq,Hkv,G,Dv) fp32, lse (B,Sq,Hkv,G) fp32).
    """
    B, Sq, Hkv, G, D = q.shape
    n_blocks = k.shape[0]
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, xs):
        m, l, acc = carry
        blk_idx, k_blk, v_blk = xs
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", q, k_blk,
                       preferred_element_type=jnp.float32)
        s = jnp.where(_block_mask(q_pos, k_pos, Sk, causal, window)
                      [None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        # P in the model dtype for the PV matmul (flash-standard); fp32 row
        # sums keep the softmax normalization exact.
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, Sq, Hkv, G, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (jnp.arange(n_blocks), k, v))
    l = jnp.maximum(l, 1e-30)
    return acc / l[..., None], m + jnp.log(l)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_xla(q, k, v, causal, window, q_offset, block_k, scale, Sk):
    out, _ = _flash_fwd(q, k, v, causal, window, q_offset, block_k, scale, Sk)
    return out


def _flash_attention_xla_fwd(q, k, v, causal, window, q_offset, block_k, scale, Sk):
    out, lse = _flash_fwd(q, k, v, causal, window, q_offset, block_k, scale, Sk)
    return out, (q, k, v, out, lse)


def _flash_attention_xla_bwd(causal, window, q_offset, block_k, scale, Sk,
                             res, d_out):
    """Flash-style backward: recompute P per KV block from (q, k, lse) —
    O(block) memory instead of materializing the S² scan residuals that the
    autodiff of the forward scan would store (§Perf pair-3 iteration 1)."""
    q, k, v, out, lse = res
    B, Sq, Hkv, G, D = q.shape
    n_blocks = k.shape[0]
    q_pos = jnp.arange(Sq) + q_offset
    delta = jnp.sum(d_out * out, axis=-1)  # (B,Sq,Hkv,G) fp32
    dtype = q.dtype

    def body(dq_acc, xs):
        blk_idx, k_blk, v_blk = xs
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", q, k_blk,
                       preferred_element_type=jnp.float32)
        mask = _block_mask(q_pos, k_pos, Sk, causal, window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # masked -> exp(-inf) = 0
        p_lo = p.astype(dtype)
        dv_blk = jnp.einsum("bqhgk,bqhgd->bkhd", p_lo, d_out.astype(dtype),
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bkhd->bqhgk", d_out.astype(dtype), v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        ds_lo = ds.astype(dtype)
        dq_acc = dq_acc + jnp.einsum("bqhgk,bkhd->bqhgd", ds_lo, k_blk,
                                     preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bqhgk,bqhgd->bkhd", ds_lo, q,
                            preferred_element_type=jnp.float32)
        return dq_acc, (dk_blk.astype(k_blk.dtype), dv_blk.astype(v_blk.dtype))

    dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (jnp.arange(n_blocks), k, v))
    # dq is w.r.t. the pre-scaled q; the caller's scaling is outside the vjp
    return dq.astype(q.dtype), dk, dv


_flash_attention_xla.defvjp(_flash_attention_xla_fwd, _flash_attention_xla_bwd)


def chunked_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_k: int = 512,
    scale: Optional[float] = None,
    seq_shard_mode: str = "auto",
) -> jnp.ndarray:
    """Online-softmax attention, scanning KV blocks, with a flash-style
    custom VJP. Returns (B, Sq, Hq, Dv)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # Sharding strategy (§Perf pair-2 iteration 1): when the head count does
    # not divide the model axis (qwen2: 28 heads on 16), GSPMD falls back to
    # sharding the QK contraction dim and all-reduces the full score tensor
    # per KV block (~TBs of wire). Instead we sequence-shard the queries over
    # "model" and replicate K/V — scores stay chip-local.
    msize = mesh_axis_size("model")
    seq_shard = (
        seq_shard_mode == "auto"
        and msize > 1 and Hq % msize != 0 and Sq % msize == 0 and Sq > 1
    )
    if seq_shard:
        q = constrain(q, "data", "model", None, None)
        k = constrain(k, "data", None, None, None)
        v = constrain(v, "data", None, None, None)

    block_k = min(block_k, Sk)
    pad = (-Sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (Sk + pad) // block_k

    # matmul operands stay in the model dtype (bf16 on the MXU, fp32 in fp32
    # tests); softmax statistics are always fp32.
    qg = (q * scale).reshape(B, Sq, Hkv, G, D)
    kb = k.reshape(B, n_blocks, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, block_k, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    out = _flash_attention_xla(qg, kb, vb, causal, window, q_offset, block_k,
                               scale, Sk)
    return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)


def naive_attention(q, k, v, *, causal=True, window=0, q_offset=0, scale=None):
    """O(S^2)-memory reference attention (tests / tiny models only)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------


def init_gqa(key, cfg, dtype, *, cross: bool = False):
    """Weights for grouped-query attention (optionally a cross-attn variant)."""
    ks = split_keys(key, 4)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": init_linear(ks[0], cfg.d_model, H * D, dtype, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], cfg.d_model, Hkv * D, dtype, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], cfg.d_model, Hkv * D, dtype, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], H * D, cfg.d_model, dtype, scale=1.0 / math.sqrt(2 * max(cfg.num_layers, 1))),
    }
    return p


def gqa_forward(
    p,
    cfg,
    x: jnp.ndarray,  # (B, S, d_model)
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: int = 0,
    kv_src: Optional[jnp.ndarray] = None,  # cross-attention source
    use_rope: bool = True,
    block_k: int = 512,
):
    B, S, _ = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    src = x if kv_src is None else kv_src
    Sk = src.shape[1]
    q = linear(p["wq"], x).reshape(B, S, H, D)
    k = linear(p["wk"], src).reshape(B, Sk, Hkv, D)
    v = linear(p["wv"], src).reshape(B, Sk, Hkv, D)
    if use_rope and kv_src is None:
        pos = positions if positions is not None else jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=causal, window=window, block_k=block_k,
                            seq_shard_mode=cfg.attn_seq_shard)
    return linear(p["wo"], out.reshape(B, S, H * D))


def gqa_prefill(p, cfg, x, *, window: int = 0, block_k: int = 512):
    """Forward that also returns the KV cache contents (roped K, V)."""
    B, S, _ = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(p["wq"], x).reshape(B, S, H, D)
    k = linear(p["wk"], x).reshape(B, S, Hkv, D)
    v = linear(p["wv"], x).reshape(B, S, Hkv, D)
    pos = jnp.arange(S)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=True, window=window, block_k=block_k,
                            seq_shard_mode=cfg.attn_seq_shard)
    return linear(p["wo"], out.reshape(B, S, H * D)), (k, v)


def init_gqa_cache(cfg, batch: int, max_len: int, dtype):
    """Ring buffer when sliding window is active, else full-length cache."""
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    Hkv, D = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, slots, Hkv, D), dtype),
        "v": jnp.zeros((batch, slots, Hkv, D), dtype),
    }


def gqa_decode(
    p,
    cfg,
    x: jnp.ndarray,  # (B, 1, d_model)
    cache,
    pos,  # scalar int32 (shared position), or (B,) int32 per-row positions
    *,
    window: int = 0,
):
    """Single-token decode against the cache. Returns (out, new_cache).

    ``pos`` may be a scalar (every row at the same position — the lockstep
    launcher) or a ``(B,)`` vector giving each batch row its own position
    (the serving plane's slot-managed decode, where requests join and
    leave mid-flight). Every op is row-independent in both modes: row
    ``b``'s output depends only on row ``b``'s token, position and cache
    row, which is what makes the serving plane's per-request bitwise pin
    possible.
    """
    B = x.shape[0]
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    slots = cache["k"].shape[1]
    q = linear(p["wq"], x).reshape(B, 1, H, D)
    k = linear(p["wk"], x).reshape(B, 1, Hkv, D)
    v = linear(p["wv"], x).reshape(B, 1, Hkv, D)
    slot_idx = jnp.arange(slots)
    win = window if window else slots
    if jnp.ndim(pos) == 0:
        pos_arr = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, pos_arr, cfg.rope_theta)
        k = apply_rope(k, pos_arr, cfg.rope_theta)

        write = pos % slots  # ring write (== pos when full-length cache)
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, write, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, write, 0, 0))
        if window == 0 and cfg.sliding_window == 0:
            valid = slot_idx <= pos
        else:
            # ring buffer: a slot holds token (pos - ((write - i) % slots));
            # valid iff its age < min(window, pos+1)
            age = (write - slot_idx) % slots
            valid = age < jnp.minimum(win, pos + 1)
        maskb = valid[None, None, None, :]
    else:
        # per-row positions: rope by (B,1) positions, one-hot where-write
        # into each row's own slot, per-row validity mask
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

        write = pos % slots  # (B,)
        hit = slot_idx[None, :] == write[:, None]  # (B, slots)
        ck = jnp.where(hit[:, :, None, None], k.astype(cache["k"].dtype), cache["k"])
        cv = jnp.where(hit[:, :, None, None], v.astype(cache["v"].dtype), cache["v"])
        if window == 0 and cfg.sliding_window == 0:
            valid = slot_idx[None, :] <= pos[:, None]
        else:
            age = (write[:, None] - slot_idx[None, :]) % slots
            valid = age < jnp.minimum(win, pos[:, None] + 1)
        maskb = valid[:, None, None, :]

    G = H // Hkv
    qg = (q * (1.0 / math.sqrt(D))).reshape(B, Hkv, G, D).astype(ck.dtype)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, ck, preferred_element_type=jnp.float32)
    s = jnp.where(maskb, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", pr.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, H * D).astype(x.dtype)
    return linear(p["wo"], out), {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype):
    ks = split_keys(key, 6)
    H = cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {}
    if cfg.q_lora_rank:
        p["wdq"] = init_linear(ks[0], cfg.d_model, cfg.q_lora_rank, dtype)
        p["q_norm"] = init_rmsnorm(cfg.q_lora_rank, dtype)
        p["wuq"] = init_linear(ks[1], cfg.q_lora_rank, H * qk, dtype)
    else:
        p["wq"] = init_linear(ks[0], cfg.d_model, H * qk, dtype)
    p["wdkv"] = init_linear(ks[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim, dtype)
    p["kv_norm"] = init_rmsnorm(cfg.kv_lora_rank, dtype)
    # W_ukv maps latent -> per-head (k_nope || v)
    p["wukv"] = init_linear(
        ks[3], cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype
    )
    p["wo"] = init_linear(
        ks[4], H * cfg.v_head_dim, cfg.d_model, dtype,
        scale=1.0 / math.sqrt(2 * max(cfg.num_layers, 1)),
    )
    return p


def _mla_queries(p, cfg, x):
    B, S, _ = x.shape
    H = cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        q = linear(p["wuq"], rmsnorm(p["q_norm"], linear(p["wdq"], x), cfg.norm_eps))
    else:
        q = linear(p["wq"], x)
    q = q.reshape(B, S, H, qk)
    return jnp.split(q, [cfg.qk_nope_dim], axis=-1)  # q_nope, q_rope


def _mla_latent(p, cfg, x):
    """Compressed per-token latent: (c_kv normalized, k_rope un-roped)."""
    ckv = linear(p["wdkv"], x)
    c, k_rope = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    return rmsnorm(p["kv_norm"], c, cfg.norm_eps), k_rope


def mla_forward(p, cfg, x, *, positions=None, window: int = 0, block_k: int = 512,
                return_cache: bool = False):
    """Full-sequence MLA (train / prefill), under the ``mla`` name scope.
    Rotary embedding and softmax scale follow ``cfg`` (YaRN where set)."""
    with jax.named_scope("mla"):
        return _mla_forward(p, cfg, x, positions, window, block_k, return_cache)


def _mla_forward(p, cfg, x, positions, window, block_k, return_cache):
    B, S, _ = x.shape
    H = cfg.num_heads
    pos = positions if positions is not None else jnp.arange(S)
    inv_freq, rope_mscale = rope_for(cfg, cfg.qk_rope_dim)
    q_nope, q_rope = _mla_queries(p, cfg, x)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta, inv_freq, rope_mscale)
    c, k_rope = _mla_latent(p, cfg, x)
    k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta, inv_freq,
                        rope_mscale)  # (B,S,1,rope)
    kv = linear(p["wukv"], c).reshape(B, S, H, cfg.qk_nope_dim + cfg.v_head_dim)
    k_nope, v = jnp.split(kv, [cfg.qk_nope_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, cfg.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = softmax_scale_for(cfg, cfg.qk_nope_dim + cfg.qk_rope_dim)
    out = chunked_attention(q, k, v, causal=True, window=window, block_k=block_k,
                            scale=scale, seq_shard_mode=cfg.attn_seq_shard)
    y = linear(p["wo"], out.reshape(B, S, H * cfg.v_head_dim))
    if return_cache:
        return y, (c, k_rope[:, :, 0, :])
    return y


def init_mla_cache(cfg, batch: int, max_len: int, dtype):
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    return {
        "c": jnp.zeros((batch, slots, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, slots, cfg.qk_rope_dim), dtype),
    }


def mla_decode(p, cfg, x, cache, pos, *, window: int = 0):
    """Single-token MLA decode.

    cfg.mla_absorb selects the latent-space path (W_uk absorbed into q,
    W_uv into the output) versus the naive path that reconstructs all
    per-head K/V from the latent every step. ``pos`` is a scalar (shared
    position) or a ``(B,)`` vector of per-row positions (serving plane) —
    see ``gqa_decode``; both modes are row-independent. Runs under the
    ``mla`` name scope.
    """
    with jax.named_scope("mla"):
        return _mla_decode(p, cfg, x, cache, pos, window)


def _mla_decode(p, cfg, x, cache, pos, window):
    B = x.shape[0]
    H = cfg.num_heads
    slots = cache["c"].shape[1]
    slot_idx = jnp.arange(slots)
    win = window if window else slots
    q_nope, q_rope = _mla_queries(p, cfg, x)  # (B,1,H,*)
    c_new, kr_new = _mla_latent(p, cfg, x)  # (B,1,kv_lora), (B,1,rope)
    inv_freq, rope_mscale = rope_for(cfg, cfg.qk_rope_dim)

    if jnp.ndim(pos) == 0:
        pos_arr = jnp.full((1,), pos, jnp.int32)
        q_rope = apply_rope(q_rope, pos_arr, cfg.rope_theta, inv_freq,
                            rope_mscale)
        kr_new = apply_rope(kr_new[:, :, None, :], pos_arr, cfg.rope_theta,
                            inv_freq, rope_mscale)[:, :, 0, :]

        write = pos % slots
        cc = jax.lax.dynamic_update_slice(cache["c"], c_new.astype(cache["c"].dtype), (0, write, 0))
        ckr = jax.lax.dynamic_update_slice(cache["kr"], kr_new.astype(cache["kr"].dtype), (0, write, 0))
        if cfg.sliding_window == 0 and window == 0:
            valid = slot_idx <= pos
        else:
            age = (write - slot_idx) % slots
            valid = age < jnp.minimum(win, pos + 1)
        maskb = valid[None, None, :]
    else:
        q_rope = apply_rope(q_rope, pos[:, None], cfg.rope_theta, inv_freq,
                            rope_mscale)
        kr_new = apply_rope(kr_new[:, :, None, :], pos[:, None],
                            cfg.rope_theta, inv_freq, rope_mscale)[:, :, 0, :]

        write = pos % slots  # (B,)
        hit = slot_idx[None, :] == write[:, None]  # (B, slots)
        cc = jnp.where(hit[:, :, None], c_new.astype(cache["c"].dtype), cache["c"])
        ckr = jnp.where(hit[:, :, None], kr_new.astype(cache["kr"].dtype), cache["kr"])
        if cfg.sliding_window == 0 and window == 0:
            valid = slot_idx[None, :] <= pos[:, None]
        else:
            age = (write[:, None] - slot_idx[None, :]) % slots
            valid = age < jnp.minimum(win, pos[:, None] + 1)
        maskb = valid[:, None, :]

    scale = softmax_scale_for(cfg, cfg.qk_nope_dim + cfg.qk_rope_dim)
    nope, vdim, rank = cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    wukv = p["wukv"]["w"].reshape(rank, H, nope + vdim)
    w_uk, w_uv = wukv[..., :nope], wukv[..., nope:]  # (rank,H,nope),(rank,H,v)

    # decode math keeps cache-dtype (bf16) matmul operands with fp32
    # accumulation — upcasting the cache would make XLA materialize fp32
    # copies of the whole cache per layer (§Perf pair-1 iteration 2)
    f32 = jnp.float32
    if cfg.mla_absorb:
        # latent-space attention: O(S·rank) per head pair, no K/V expansion
        qn = q_nope[:, 0]  # (B,H,nope)
        q_lat = jnp.einsum("bhn,rhn->bhr", qn, w_uk, preferred_element_type=f32)
        s = jnp.einsum("bhr,bkr->bhk", q_lat.astype(cc.dtype), cc,
                       preferred_element_type=f32)
        s = s + jnp.einsum("bhr,bkr->bhk", q_rope[:, 0].astype(ckr.dtype), ckr,
                           preferred_element_type=f32)
        s = jnp.where(maskb, s * scale, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhk,bkr->bhr", pr.astype(cc.dtype), cc,
                           preferred_element_type=f32)  # (B,H,rank)
        out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(w_uv.dtype), w_uv,
                         preferred_element_type=f32)
    else:
        # naive: expand the whole cache to per-head K/V every step
        kv = jnp.einsum("bkr,rhe->bkhe", cc, wukv, preferred_element_type=cc.dtype)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        qn = q_nope[:, 0].astype(kv.dtype)
        s = jnp.einsum("bhn,bkhn->bhk", qn, k_nope, preferred_element_type=f32)
        s = s + jnp.einsum("bhr,bkr->bhk", q_rope[:, 0].astype(ckr.dtype), ckr,
                           preferred_element_type=f32)
        s = jnp.where(maskb, s * scale, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhk,bkhv->bhv", pr.astype(v.dtype), v,
                         preferred_element_type=f32)

    out = out.reshape(B, 1, H * vdim).astype(x.dtype)
    return linear(p["wo"], out), {"c": cc, "kr": ckr}
