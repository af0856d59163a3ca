"""Pallas kernel: single-token (decode) attention against a long KV cache.

Flash-decoding adapted to TPU: grid (B, Hkv, Sk/block_k) with the KV-block
axis innermost, streaming the cache through VMEM once while fp32 scratch
(m, l, acc) carries the online-softmax state for the G grouped query heads.
The kernel reads the cache head-major, (B, Hkv, S, D), so each K/V block is
a (block_k, D) tile that meets the TPU's (8, 128) block rule; the wrapper
transposes the (B, S, Hkv, D) serving layout into it (a cache stored
head-major would skip that pass).
The valid-length bound (``pos``) is a scalar-prefetch operand so masked
tail blocks are skipped entirely (``pl.when``), making decode cost
proportional to the *filled* cache, not its capacity.

This is the serve_step hot loop for decode_32k / long_500k: arithmetic
intensity ≈ G flops/byte, i.e. HBM-bandwidth-bound — exactly what the
roofline table shows for the decode shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, block_k, num_kb):
    ki = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * block_k

    @pl.when(k_start <= pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, D) — the single query token per sequence
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, Dv)
    pos,  # scalar int32 — attend to slots <= pos
    *,
    scale=None,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, S, Hkv, Dv = v_cache.shape
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    pad_k = (-S) % block_k
    kk = jnp.pad(k_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k_cache
    vv = jnp.pad(v_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v_cache
    kk = kk.transpose(0, 2, 1, 3)  # head-major (B, Hkv, S, D)
    vv = vv.transpose(0, 2, 1, 3)
    nk = (S + pad_k) // block_k
    qg = q.reshape(B, Hkv, G, D)
    pos_arr = jnp.full((1,), pos, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ki, pos_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, pos_ref: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, ki, pos_ref: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, h, ki, pos_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k, num_kb=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        interpret=interpret,
    )(pos_arr, qg, kk, vv)
    return out.reshape(B, H, Dv)
