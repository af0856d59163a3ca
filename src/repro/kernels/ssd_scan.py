"""Pallas kernel: fused chunked SSD (Mamba2) scan.

TPU adaptation of the CUDA selective-scan (DESIGN.md §6): grid
(B, H, S/chunk) with the chunk axis innermost; the (P, N) state carries in
fp32 VMEM scratch across chunks. The kernel reads x head-major,
(B, H, S, P), and dt as (B, H, S, 1) columns, so every block's last two
dims are (chunk, P) or (chunk, 1) — the TPU's (8, 128)-or-whole rule; the
per-head A_log and D scalars sit whole in SMEM. Per chunk, everything is
dense MXU work:

    scores  = C · Bᵀ               (Q×N · N×Q)
    y_intra = (scores ∘ decay) · (dt·x)
    y_inter = exp(cum) · (C · state)
    state   = exp(total)·state + Σ_j exp(total-cum_j) B_j ⊗ (dt·x)_j

vs. the reference's materialized (B, nc, Q, Q, H) decay tensor, the kernel
keeps only (Q, Q) per head-chunk in VMEM — the memory win that makes
chunk=256 viable on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 in, f32 out: every dot keeps full f32 precision. At the TPU's default
# precision the MXU rounds f32 operands to bf16, and the decays and scores
# built from them put about half of a mamba2-370m prefill's outputs outside
# 2e-3 of the f32 recurrence.
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(alog_ref, d_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_scr,
            *, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    B = b_ref[0].astype(jnp.float32)  # (Q, N)
    C = c_ref[0].astype(jnp.float32)  # (Q, N)
    a = -jnp.exp(alog_ref[h]) * dt  # (Q, 1) negative log-decay
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = jj <= ii
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum), broadcast over columns: cum_i[i, j] = Σ_{k<=i} a_k
    cum_i = jax.lax.dot(
        causal.astype(jnp.float32), jnp.broadcast_to(a, (chunk, chunk)),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    cum = cum_i[:, :1]  # (Q, 1)
    total = jnp.sum(a)  # scalar: the chunk's whole log-decay

    xdt = x * dt  # (Q, P)
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (Q, Q) = C_i . B_j
    dec = cum_i - cum_i.T
    L = jnp.where(causal, jnp.exp(dec), 0.0)
    y_intra = jax.lax.dot(scores * L, xdt, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    state = state_scr[...]  # (P, N)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (Q, P)

    y = y_intra + y_inter + d_ref[h] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: exp(total)*state + sum_j exp(total - cum_j) (dt x)_j ⊗ B_j
    w = jnp.exp(total - cum)  # (Q, 1)
    state_scr[...] = jnp.exp(total) * state + jax.lax.dot_general(
        xdt * w, B, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (P, N)


def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) post-softplus
    A_log: jnp.ndarray,  # (H,)
    B_mat: jnp.ndarray,  # (B, S, N) shared across heads
    C_mat: jnp.ndarray,  # (B, S, N)
    D_vec: jnp.ndarray,  # (H,)
    *,
    chunk: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """Returns y: (B, S, H, P). (Final state stays in scratch — decode uses
    the recurrent path; prefill-with-state uses the reference.)"""
    Bsz, S, H, P = x.shape
    N = B_mat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, f"S={S} % chunk={chunk}"
    nc = S // chunk
    xh = x.transpose(0, 2, 1, 3)  # head-major (B, H, S, P)
    dth = dt.transpose(0, 2, 1)[..., None]  # (B, H, S, 1): dt as a column
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # per-head scalars, whole

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(Bsz, H, nc),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A_log.astype(jnp.float32), D_vec.astype(jnp.float32), xh, dth, B_mat,
      C_mat)
    return out.transpose(0, 2, 1, 3)
