"""Pallas kernel: fused latent-space (absorbed) MLA decode attention.

The §Perf pair-A analysis ends with: the absorbed MLA decode still reads the
compressed cache twice (score pass + combine pass) — a fused kernel reads it
once. This kernel is that next step: single-token MLA attention entirely in
latent space, streaming the (c ‖ k_rope) cache through VMEM one block at a
time with online-softmax scratch:

    s_k    = q_lat · c_k + q_rope · kr_k          (per cached token k)
    out    = Σ softmax(s)_k · c_k                 (latent-space combine)

Inputs are the *absorbed* queries (W_uk already folded in — see
repro.models.attention.mla_decode); the caller applies W_uv afterwards.
Grid: (B, S/block_k) with fp32 (m, l, acc) scratch per head block.

Arithmetic intensity ≈ 2·H flops/byte over the latent cache — with H=128
(DeepSeek-V2) this is near the bf16 ridge point, i.e. the fused kernel turns
MLA decode from bandwidth- toward compute-bound, unlike GQA decode (G≤8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, ql_ref, qr_ref, c_ref, kr_ref, o_ref, m_scr, l_scr,
            acc_scr, *, block_k, num_kb, scale):
    ki = pl.program_id(1)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * block_k

    @pl.when(k_start <= pos)
    def _compute():
        ql = ql_ref[0].astype(jnp.float32)  # (H, R)
        qr = qr_ref[0].astype(jnp.float32)  # (H, Rr)
        c = c_ref[0].astype(jnp.float32)  # (bk, R)
        kr = kr_ref[0].astype(jnp.float32)  # (bk, Rr)
        s = jax.lax.dot_general(ql, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * scale  # (H, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        # combine in latent space: the SAME c block — one HBM read serves
        # both the score and the combine pass (the fusion win)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
            p.astype(c_ref.dtype), c_ref[0], preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def mla_decode_attention_pallas(
    q_lat: jnp.ndarray,  # (B, H, R)  — absorbed queries (W_uk folded in)
    q_rope: jnp.ndarray,  # (B, H, Rr)
    c_cache: jnp.ndarray,  # (B, S, R)  — compressed latent cache
    kr_cache: jnp.ndarray,  # (B, S, Rr) — shared roped keys
    pos,  # scalar int32: attend to slots <= pos
    scale: float,
    *,
    block_k: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """Returns latent-space attention output (B, H, R)."""
    B, H, R = q_lat.shape
    _, S, Rr = kr_cache.shape
    block_k = min(block_k, S)
    pad = (-S) % block_k
    cc = jnp.pad(c_cache, ((0, 0), (0, pad), (0, 0))) if pad else c_cache
    kr = jnp.pad(kr_cache, ((0, 0), (0, pad), (0, 0))) if pad else kr_cache
    nk = (S + pad) // block_k
    pos_arr = jnp.full((1,), pos, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, ki, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, H, Rr), lambda b, ki, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_k, R), lambda b, ki, pos_ref: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Rr), lambda b, ki, pos_ref: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, ki, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, num_kb=nk, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        interpret=interpret,
    )(pos_arr, q_lat, q_rope, cc, kr)
