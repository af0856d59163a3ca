"""Shared model building blocks: initializers, linear layers, norms, RoPE.

Parameters are plain nested dicts of jnp arrays (no flax). Every module is a
pair of functions: ``init_*(key, ...) -> params`` and an apply function.
Scanned layer stacks hold parameters stacked along a leading layer axis.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float = 1.0):
    """LeCun-normal style init used for all projection matrices."""
    std = scale / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


def init_linear(key, in_dim: int, out_dim: int, dtype, bias: bool = False, scale: float = 1.0):
    p = {"w": dense_init(key, in_dim, out_dim, dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies for rotary embedding over `dim` channels."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq: Optional[jnp.ndarray] = None,
               mscale: float = 1.0) -> jnp.ndarray:
    """Rotary position embedding (halves convention).

    x: (B, S, H, D) or (B, S, D); positions: (S,) or (B, S). ``inv_freq``
    replaces the plain frequencies (YaRN's, say) and ``mscale`` scales the
    cosines and sines.
    """
    dim = x.shape[-1]
    if inv_freq is None:
        inv_freq = rope_frequencies(dim, theta)  # (dim/2,)
    pos = positions.astype(jnp.float32)
    angles = jnp.einsum("...s,f->...sf", pos, inv_freq)  # (S, d/2) or (B, S, d/2)
    if angles.ndim == 2:  # (S, d/2) -> broadcast over batch
        angles = angles[None]
    if x.ndim == 4:  # head axis present
        angles = angles[:, :, None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# YaRN (Peng et al. 2023) as DeepSeek-V2 applies it
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def yarn_frequencies(dim: int, theta: float, factor: float, max_pos: int,
                     beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN's inverse frequencies over ``dim`` channels: the plain ones
    for the fast-rotating channels, divided by ``factor`` for the slow
    ones, and a linear ramp between the channels that turn ``beta_fast``
    and ``beta_slow`` times over the ``max_pos`` original positions."""
    extra = rope_frequencies(dim, theta)
    inter = extra / factor
    low = max(math.floor(_yarn_correction_dim(beta_fast, dim, theta, max_pos)), 0)
    high = min(math.ceil(_yarn_correction_dim(beta_slow, dim, theta, max_pos)),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 where the plain frequency stays
    return inter * (1.0 - keep) + extra * keep


def rope_for(cfg, dim: int):
    """(inverse frequencies or None for plain RoPE, cos/sin scale) of a
    configuration's rotary embedding over ``dim`` channels."""
    if not cfg.yarn_factor:
        return None, 1.0
    inv_freq = yarn_frequencies(dim, cfg.rope_theta, cfg.yarn_factor,
                                cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                                cfg.yarn_beta_slow)
    mscale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
              / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return inv_freq, mscale


def softmax_scale_for(cfg, head_dim: int) -> float:
    """1/sqrt(head_dim), times YaRN's mscale(mscale_all_dim) squared where
    the configuration scales its rotary embedding so."""
    scale = 1.0 / math.sqrt(head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def causal_mask(q_len: int, kv_len: int, q_offset: int = 0) -> jnp.ndarray:
    """Boolean (q_len, kv_len) mask; True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    return k_pos <= q_pos


def sliding_window_mask(q_len: int, kv_len: int, window: int, q_offset: int = 0) -> jnp.ndarray:
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    return (k_pos <= q_pos) & (k_pos > q_pos - window)
