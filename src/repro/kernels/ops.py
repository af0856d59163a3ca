"""Jit'd dispatch wrappers around the Pallas kernels.

``backend="pallas"`` runs the TPU kernels, ``backend="ref"`` the pure-jnp
oracles. Model code and benchmarks call these; tests sweep both and assert
equality. This module is the one place that decides interpret mode: at
trace time, kernels run interpreted when the default backend is the CPU
and compiled everywhere else. Importing it touches no backend.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mla_decode import mla_decode_attention_pallas
from repro.kernels.nstep_returns import nstep_returns_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.vtrace import vtrace_returns_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("gamma", "backend"))
def nstep_returns(rewards, dones, bootstrap, gamma: float, backend: str = "pallas"):
    if backend == "ref":
        return _ref.nstep_returns_ref(rewards, dones, bootstrap, gamma)
    return nstep_returns_pallas(rewards, dones, bootstrap, gamma,
                                interpret=_interpret())


@partial(jax.jit, static_argnames=("gamma", "rho_bar", "c_bar", "backend"))
def vtrace_returns(rewards, dones, values, bootstrap, rho, gamma: float,
                   rho_bar: float = 1.0, c_bar: float = 1.0,
                   backend: str = "pallas"):
    if backend == "ref":
        return _ref.vtrace_returns_ref(rewards, dones, values, bootstrap, rho,
                                       gamma, rho_bar, c_bar)
    return vtrace_returns_pallas(rewards, dones, values, bootstrap, rho, gamma,
                                 rho_bar, c_bar, interpret=_interpret())


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "backend"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
                    backend: str = "pallas"):
    if backend == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("block_k", "backend"))
def decode_attention(q, k_cache, v_cache, pos, *, block_k=512, backend: str = "pallas"):
    if backend == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, pos)
    return decode_attention_pallas(
        q, k_cache, v_cache, pos, block_k=block_k, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("scale", "block_k", "backend"))
def mla_decode_attention(q_lat, q_rope, c_cache, kr_cache, pos, scale: float,
                         *, block_k=512, backend: str = "pallas"):
    if backend == "ref":
        return _ref.mla_decode_attention_ref(q_lat, q_rope, c_cache, kr_cache,
                                             pos, scale)
    return mla_decode_attention_pallas(
        q_lat, q_rope, c_cache, kr_cache, pos, scale, block_k=block_k,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan(x, dt, A_log, B_mat, C_mat, D_vec, *, chunk=128, backend: str = "pallas"):
    if backend == "ref":
        y, _ = _ref.ssd_scan_ref(x, dt, A_log, B_mat, C_mat, D_vec)
        return y
    return ssd_scan_pallas(x, dt, A_log, B_mat, C_mat, D_vec, chunk=chunk,
                           interpret=_interpret())
