"""Plain copy of the pixel game the PAAC cells train on, and its frame stack.

The game ("CatchPixels", an on-device stand-in for ALE): a ball falls from
the top of an 84x84 canvas at a random column with a random horizontal
drift, bounces off the side walls, and a paddle on the bottom rows catches
it (+1) or misses it (-1). An episode is ``lives`` balls. Each agent step
repeats its action for ``action_repeat`` raw frames; a reset plays 1 to
``max_noops`` no-op frames. Finished environments reset in the same step,
and the four-frame stack restarts from the new frame.

Written from the game's rules, one environment at a time, and vectorised
with ``jax.vmap``; the random draws use the same keys in the same order as
the game's definition, so a seed gives the same episode here as there.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

SIZE = 84
PADDLE_W = 8
BALL = 3
ROW_BOTTOM = SIZE - 4
ACTIONS = 3  # left, stay, right
NOOP = 1


def spawn_ball(key):
    k_col, k_vx = jax.random.split(key)
    col = jax.random.randint(k_col, (), BALL, SIZE - BALL)
    vx = jax.random.randint(k_vx, (), -2, 3)
    return jnp.stack([jnp.int32(0), col, jnp.int32(2), vx])


def frame(state, action, key):
    """One raw frame: move the paddle, move and bounce the ball, score."""
    paddle = jnp.clip(state["paddle"] + (action - 1) * 3, PADDLE_W,
                      SIZE - PADDLE_W)
    row, col, vy, vx = state["ball"]
    row, col = row + vy, col + vx
    vx = jnp.where((col <= BALL) | (col >= SIZE - BALL), -vx, vx)
    col = jnp.clip(col, BALL, SIZE - BALL)
    bottom = row >= ROW_BOTTOM
    caught = bottom & (jnp.abs(col - paddle) <= PADDLE_W)
    reward = jnp.where(bottom, jnp.where(caught, 1.0, -1.0), 0.0)
    lives = state["lives"] - bottom.astype(jnp.int32)
    ball = jnp.where(bottom, spawn_ball(key), jnp.stack([row, col, vy, vx]))
    return {"ball": ball, "paddle": paddle, "lives": lives}, reward, lives <= 0


def reset_one(key, lives: int, max_noops: int):
    k_ball, k_paddle, k_noops = jax.random.split(key, 3)
    state = {
        "ball": spawn_ball(k_ball),
        "paddle": jax.random.randint(k_paddle, (), PADDLE_W, SIZE - PADDLE_W),
        "lives": jnp.int32(lives),
    }
    n_noops = jax.random.randint(k_noops, (), 1, max_noops + 1)
    # every no-op frame draws a respawn from the reset's own key
    return jax.lax.fori_loop(
        0, n_noops, lambda _, s: frame(s, jnp.int32(NOOP), key)[0], state)


def step_one(state, action, key, repeat: int):
    reward = jnp.zeros(())
    done = jnp.zeros((), bool)
    for _ in range(repeat):
        key, sub = jax.random.split(key)
        state, r, d = frame(state, action, sub)
        reward = reward + r
        done = done | d
    return state, reward, done


def render(state):
    rows = jnp.arange(SIZE)[:, None]
    cols = jnp.arange(SIZE)[None, :]
    ball = ((jnp.abs(rows - state["ball"][0]) <= BALL // 2)
            & (jnp.abs(cols - state["ball"][1]) <= BALL // 2))
    paddle = (rows >= ROW_BOTTOM) & (jnp.abs(cols - state["paddle"]) <= PADDLE_W)
    return jnp.clip(ball.astype(jnp.float32) + paddle.astype(jnp.float32), 0, 1)


class StackedGame(NamedTuple):
    """``n`` games with a ``stack``-frame observation (n, 84, 84, stack)."""

    n: int
    stack: int = 4
    lives: int = 5
    repeat: int = 4
    max_noops: int = 30

    def _reset(self, keys):
        return jax.vmap(lambda k: reset_one(k, self.lives, self.max_noops))(keys)

    def reset(self, key):
        inner = self._reset(jax.random.split(key, self.n))
        first = jax.vmap(render)(inner)
        return {"inner": inner,
                "stack": jnp.repeat(first[..., None], self.stack, axis=-1)}

    @staticmethod
    def observe(state):
        return state["stack"]

    def step(self, state, actions, key):
        keys = jax.random.split(key, 2 * self.n).reshape(2, self.n, -1)
        moved, reward, done = jax.vmap(
            lambda s, a, k: step_one(s, a, k, self.repeat))(
                state["inner"], actions, keys[0])
        fresh = self._reset(keys[1])
        inner = jax.tree_util.tree_map(
            lambda r, m: jnp.where(done.reshape((-1,) + (1,) * (m.ndim - 1)),
                                   r, m),
            fresh, moved)
        obs = jax.vmap(render)(inner)
        stack = jnp.concatenate([state["stack"][..., 1:], obs[..., None]], -1)
        restart = jnp.repeat(obs[..., None], self.stack, axis=-1)
        stack = jnp.where(done[:, None, None, None], restart, stack)
        return {"inner": inner, "stack": stack}, stack, reward, done
