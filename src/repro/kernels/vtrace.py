"""Pallas kernel: batched V-trace targets (Espeholt et al. 2018, eqs. 1–4).

Like ``nstep_returns``, the recursion is sequential in time and data-parallel
over actors; the asynchronous pipeline's learner folds truncated-importance
corrections into the n-step recursion:

    δ_t = min(ρ̄, rho_t)·(r_t + γ_t·V_{t+1} - V_t)     γ_t = γ·(1-done_t)
    A_t = δ_t + γ_t·min(c̄, rho_t)·A_{t+1}             A_T = 0
    v_t = V_t + A_t
    pg_adv_t = min(ρ̄, rho_t)·(r_t + γ_t·v_{t+1} - V_t)

The kernel uses ``nstep_returns``' time-major tiling: ``(T, block_e)``
blocks with actors on the lane axis (grid over E/block_e), walking t_max
backwards one sublane row at a time, producing both the value targets and
the policy-gradient advantages in one HBM round-trip per tile.

VMEM budget: (7·T + 8)·block_e fp32 — block_e=256, T=4096 → 29 MB;
use block_e=128 for long horizons.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.nstep_returns import time_major_tiles


def _kernel(r_ref, nd_ref, v_ref, vnext_ref, rho_ref, boot_ref,
            vs_ref, adv_ref, *, gamma: float, rho_bar: float, c_bar: float,
            T: int):
    def body(i, carry):
        acc, vs_next = carry  # (1, block_e) rows A_{t+1}, v_{t+1}
        t = T - 1 - i
        row = pl.ds(t, 1)
        r_t, v_t = r_ref[row, :], v_ref[row, :]
        rho_t = rho_ref[row, :]
        disc = gamma * nd_ref[row, :]
        rc = jnp.minimum(rho_t, rho_bar)
        c = jnp.minimum(rho_t, c_bar)
        delta = rc * (r_t + disc * vnext_ref[row, :] - v_t)
        acc = delta + disc * c * acc
        vs_t = v_t + acc
        vs_ref[row, :] = vs_t
        adv_ref[row, :] = rc * (r_t + disc * vs_next - v_t)
        return acc, vs_t

    boot = boot_ref[...]  # v_T = V(s_{T+1})
    jax.lax.fori_loop(0, T, body, (jnp.zeros_like(boot), boot))


def vtrace_returns_pallas(
    rewards: jnp.ndarray,  # (E, T)
    dones: jnp.ndarray,  # (E, T) bool
    values: jnp.ndarray,  # (E, T)
    bootstrap: jnp.ndarray,  # (E,)
    rho: jnp.ndarray,  # (E, T) unclipped importance ratios
    gamma: float,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    *,
    block_e: int = 256,
    interpret: bool,
):
    """Returns ``(vs, pg_adv)``, both (E, T) fp32 — the Pallas twin of
    ``repro.core.returns.vtrace_returns``."""
    E, T = rewards.shape
    nd = 1.0 - dones.astype(jnp.float32)
    v = values.astype(jnp.float32)
    b = bootstrap.astype(jnp.float32)
    vn = jnp.concatenate([v[:, 1:], b[:, None]], axis=1)
    tile, grid, mats, rows = time_major_tiles(
        block_e, E, [rewards, nd, v, vn, rho], [b])
    mat = pl.BlockSpec((T, tile), lambda e: (0, e))
    vs, adv = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, rho_bar=rho_bar, c_bar=c_bar,
                          T=T),
        grid=grid,
        in_specs=[mat] * 5 + [pl.BlockSpec((1, tile), lambda e: (0, e))],
        out_specs=(mat, mat),
        out_shape=(jax.ShapeDtypeStruct(mats[0].shape, jnp.float32),) * 2,
        interpret=interpret,
    )(*mats, *rows)
    return vs[:, :E].T, adv[:, :E].T
