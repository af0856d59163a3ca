"""Pallas kernel: batched n-step discounted returns (Algorithm 1, 13–15).

The recursion R_t = r_t + γ·(1-done_t)·R_{t+1} is sequential in time but
embarrassingly parallel over actors — PAAC's central observation. The
kernel works on time-major ``(T, block_e)`` tiles: actors sit on the
128-wide lane axis (grid over E/block_e) and the backward walk over t_max
reads and writes one sublane row per step; one HBM round-trip per tile
instead of t_max tiny host-side ops.

Tiling rule (the TPU's (8, 128) block constraint): an env tile is all of E
when E fits in one block, otherwise ``block_e`` rounded up to a multiple of
128 with E padded to a whole number of tiles. The bootstrap rides as a
``(1, E)`` row under the same rule.

VMEM budget: (3·T + 8)·block_e fp32 — block_e=256, T=4096 → 12 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def time_major_tiles(block_e: int, E: int, mats, rows):
    """Transpose (E, T) matrices to padded (T, E') and lift (E,) vectors to
    padded (1, E') rows. The lane-axis tile is all E actors when they fit in
    one block, else ``block_e`` rounded up to the 128-lane width, and E' is
    a whole number of tiles. Returns ``(tile, grid, mats, rows)``."""
    block_e = -(-block_e // LANES) * LANES
    tile = E if E <= block_e else block_e
    pad = (-E) % tile
    mats = [jnp.pad(m.astype(jnp.float32).T, ((0, 0), (0, pad))) for m in mats]
    rows = [jnp.pad(r.astype(jnp.float32)[None, :], ((0, 0), (0, pad)))
            for r in rows]
    return tile, ((E + pad) // tile,), mats, rows


def _kernel(r_ref, nd_ref, boot_ref, out_ref, *, gamma: float, T: int):
    def body(i, carry):  # carry: (1, block_e) row R_{t+1}
        t = T - 1 - i
        carry = r_ref[pl.ds(t, 1), :] + gamma * nd_ref[pl.ds(t, 1), :] * carry
        out_ref[pl.ds(t, 1), :] = carry
        return carry

    jax.lax.fori_loop(0, T, body, boot_ref[...])


def nstep_returns_pallas(
    rewards: jnp.ndarray,  # (E, T)
    dones: jnp.ndarray,  # (E, T) bool
    bootstrap: jnp.ndarray,  # (E,)
    gamma: float,
    *,
    block_e: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    E, T = rewards.shape
    nd = 1.0 - dones.astype(jnp.float32)
    tile, grid, (r, nd), (b,) = time_major_tiles(
        block_e, E, [rewards, nd], [bootstrap])
    mat = pl.BlockSpec((T, tile), lambda e: (0, e))
    out = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, T=T),
        grid=grid,
        in_specs=[mat, mat, pl.BlockSpec((1, tile), lambda e: (0, e))],
        out_specs=mat,
        out_shape=jax.ShapeDtypeStruct(r.shape, jnp.float32),
        interpret=interpret,
    )(r, nd, b)
    return out[:, :E].T
