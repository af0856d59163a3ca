"""Each kernel at the widths of the configuration that would call it.

One ``KernelCase`` per ``ops.py`` entry: its shapes in a published
configuration (``source``), seeded random inputs, the static arguments its
``ops`` wrapper and ``*_pallas`` function share, and the tolerance its
output must meet against the ``ref.py`` twin. ``kernel_cases()`` gives the
full widths — what the compile-for-the-chip tests compile and
``chip_smoke.py`` runs on the chip; ``kernel_cases(tiny=True)`` gives
shapes small enough to interpret on a CPU.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mla_decode import mla_decode_attention_pallas
from repro.kernels.nstep_returns import nstep_returns_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.vtrace import vtrace_returns_pallas


@dataclass(frozen=True)
class KernelCase:
    name: str
    source: str
    make: Callable  # key -> tuple of positional array inputs
    op: Callable  # the ops.py entry (takes backend="pallas" | "ref")
    pallas: Callable  # the *_pallas function (takes interpret=...)
    tol: float  # rtol = atol against the ref twin
    kwargs: Dict = field(default_factory=dict)  # static, shared by both


def _returns_inputs(E, T):
    def make(key):
        ks = jax.random.split(key, 5)
        return (jax.random.normal(ks[0], (E, T)),
                jax.random.bernoulli(ks[1], 0.25, (E, T)),
                jax.random.normal(ks[2], (E, T)),
                jax.random.normal(ks[3], (E,)),
                jnp.exp(0.5 * jax.random.normal(ks[4], (E, T))))
    return make


def _nstep_inputs(E, T):
    vt = _returns_inputs(E, T)

    def make(key):
        r, d, _, b, _ = vt(key)
        return r, d, b
    return make


def _flash_inputs(B, S, H, Hkv, D, dtype):
    def make(key):
        ks = jax.random.split(key, 3)
        return (jax.random.normal(ks[0], (B, S, H, D), dtype),
                jax.random.normal(ks[1], (B, S, Hkv, D), dtype),
                jax.random.normal(ks[2], (B, S, Hkv, D), dtype))
    return make


def _decode_inputs(B, S, H, Hkv, D, dtype):
    def make(key):
        ks = jax.random.split(key, 3)
        return (jax.random.normal(ks[0], (B, H, D), dtype),
                jax.random.normal(ks[1], (B, S, Hkv, D), dtype),
                jax.random.normal(ks[2], (B, S, Hkv, D), dtype),
                jnp.asarray(S - S // 3, jnp.int32))  # a partly filled cache
    return make


def _mla_inputs(B, S, H, R, Rr, dtype):
    def make(key):
        ks = jax.random.split(key, 4)
        return (jax.random.normal(ks[0], (B, H, R), dtype),
                jax.random.normal(ks[1], (B, H, Rr), dtype),
                jax.random.normal(ks[2], (B, S, R), dtype),
                jax.random.normal(ks[3], (B, S, Rr), dtype),
                jnp.asarray(S - S // 3, jnp.int32))
    return make


def _ssd_inputs(B, S, H, P, N):
    def make(key):
        ks = jax.random.split(key, 4)
        return (jax.random.normal(ks[0], (B, S, H, P)),
                jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
                jnp.log(jnp.linspace(1.0, 16.0, H)),
                jax.random.normal(ks[2], (B, S, N)),
                jax.random.normal(ks[3], (B, S, N)),
                jnp.ones((H,)))
    return make


def kernel_cases(tiny: bool = False) -> List[KernelCase]:
    """Full widths by default; ``tiny`` shrinks every shape for the
    interpreter while keeping each kernel's tiling path."""
    E, T = (4, 2) if tiny else (32, 5)  # PAAC §5.1: n_e=32, t_max=5
    # qwen2-7b: 28 heads, 4 KV heads, head_dim 128
    s_pre, s_dec, b_dec = (128, 256, 2) if tiny else (2048, 4096, 8)
    # deepseek-v2: 128 heads, kv_lora 512, qk_rope 64
    mla = (2, 256, 8, 64, 16) if tiny else (8, 4096, 128, 512, 64)
    # mamba2-370m: d_inner 2048 = 32 heads x 64, ssm_state 128
    ssd = (1, 256, 2, 16, 32) if tiny else (4, 2048, 32, 64, 128)
    bf16 = jnp.bfloat16
    # fp32 paths: the kernels and the twins differ only in summation order;
    # bf16 paths: the output itself is rounded to bf16 (2^-8 relative)
    return [
        KernelCase("nstep_returns", "paper §5.1 n_e=32, t_max=5",
                   _nstep_inputs(E, T), ops.nstep_returns,
                   nstep_returns_pallas, 1e-5, {"gamma": 0.99}),
        KernelCase("vtrace_returns", "paper §5.1 n_e=32, t_max=5",
                   _returns_inputs(E, T), ops.vtrace_returns,
                   vtrace_returns_pallas, 1e-5,
                   {"gamma": 0.99, "rho_bar": 1.0, "c_bar": 1.0}),
        KernelCase("flash_attention", "qwen2-7b prefill",
                   _flash_inputs(1, s_pre, 28, 4, 128, bf16),
                   ops.flash_attention, flash_attention_pallas, 2e-2,
                   {"causal": True, "block_q": 128, "block_k": 128}),
        KernelCase("decode_attention", "qwen2-7b decode",
                   _decode_inputs(b_dec, s_dec, 28, 4, 128, bf16),
                   ops.decode_attention, decode_attention_pallas, 2e-2,
                   {"block_k": 128 if tiny else 512}),
        KernelCase("mla_decode_attention", "deepseek-v2-236b MLA decode",
                   _mla_inputs(*mla, bf16), ops.mla_decode_attention,
                   mla_decode_attention_pallas, 2e-2,
                   {"scale": 1.0 / math.sqrt(128 + 64),
                    "block_k": 128 if tiny else 512}),
        KernelCase("ssd_scan", "mamba2-370m prefill",
                   _ssd_inputs(*ssd), ops.ssd_scan, ssd_scan_pallas, 2e-3,
                   {"chunk": 64 if tiny else 128}),
    ]
