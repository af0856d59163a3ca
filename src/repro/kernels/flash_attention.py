"""Pallas kernel: blocked online-softmax (flash) attention — prefill path.

TPU-native tiling: grid (B, H, Sq/block_q, Sk/block_k); the last grid axis
is innermost on TPU so fp32 scratch (m, l, acc) persists across KV blocks
for a fixed query block. Q/K/V tiles live in VMEM with MXU-aligned shapes
(block_q × D and block_k × D, D a multiple of 64/128). Causal and
sliding-window masks skip fully-masked KV blocks via ``pl.when``
(no FLOPs and no HBM reads for the skipped tiles on real hardware).

GQA: the KV-head index is derived in the BlockSpec index map (h // group),
so K/V stay un-expanded in HBM — the kernel's bandwidth advantage for
kv<<H configs like glm4-9b (kv=2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, block_q, block_k, seq_k, causal, window, num_kb,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level mask decisions (static shapes, dynamic offsets)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run_w = k_start + block_k - 1 > q_start - window
        run = run & run_w if causal else run_w

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
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


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    scale=None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Sk, Hkv, Dv = v.shape
    G = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qq = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kk = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vv = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    # layout: (B, H, S, D) blocks
    qq = qq.transpose(0, 2, 1, 3)
    kk = kk.transpose(0, 2, 1, 3)
    vv = vv.transpose(0, 2, 1, 3)
    nq = (Sq + pad_q) // block_q
    nk = (Sk + pad_k) // block_k

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=block_q, block_k=block_k,
            seq_k=Sk, causal=causal, window=window, num_kb=nk,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pad_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),  # running max
            pltpu.VMEM((block_q,), jnp.float32),  # running denom
            pltpu.VMEM((block_q, Dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qq, kk, vv)
    out = out.transpose(0, 2, 1, 3)  # (B, Sq+pad, H, Dv)
    return out[:, :Sq]
