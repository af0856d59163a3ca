"""Fixed-width jitted decode engine shared by every scheduler mode.

The bitwise-equivalence guarantee rests on two properties this module is
careful to preserve:

* **One compiled program.** The decode step is jitted at a fixed batch
  width ``max_slots`` and every run — continuous with random join/leave
  traffic, lockstep generate-then-drain, a solo single-request run —
  executes the *same* compiled step. No shape ever depends on how many
  requests happen to be resident.
* **Row independence.** Every op in the step is per-row: the per-row
  position paths in ``gqa_decode``/``mla_decode`` (one-hot cache writes,
  per-row masks), the pos-free Mamba2 recurrence, and per-request RNG —
  token ``t`` of a request with stream root ``seed`` is sampled with
  ``fold_in(PRNGKey(seed), t)``, never from a batch-shared key. Row
  ``b``'s outputs therefore depend only on row ``b``'s token, position,
  seed and cache row.

Together: a request's sampled tokens are bitwise identical whatever
co-resides in the batch — the pin ``tests/test_serving.py`` enforces
across attention and SSM backbones.

Stale cache rows need no zeroing between leases: admission scatters a
freshly prefilled row over the slot, attention masks any position beyond
the row's own ``pos`` to ``NEG_INF`` (exp -> exactly 0), and the SSM
state is fully overwritten by prefill.

Prefill is **exact-length** (one jit per distinct prompt length, batch
1) because right-padding would corrupt the SSM recurrence; the small
cache is then scattered into the leased row of the big cache in one
jitted donating dispatch. Traffic sources should restrict themselves to
a small prompt-length alphabet to bound compilations.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_policy_cache, policy_decode, policy_prefill


class DecodeEngine:
    """W-wide decode batch over the unified policy API.

    Host-side per-slot bookkeeping (``pos``/``tindex``/``seeds``) stays in
    numpy so the step dispatch never reads device memory; the token fed
    back each step stays a device array end to end.
    """

    def __init__(self, cfg, params, *, max_slots: int, max_len: int):
        if cfg.family == "cnn":
            raise ValueError("serving needs a token-model family, not cnn")
        if cfg.is_encoder_decoder or cfg.modality == "vision":
            raise ValueError(
                "serving supports text token models only (no encoder-"
                "decoder / vision prefix plumbing on the admission path)")
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        W = max_slots
        self._pos = np.zeros(W, np.int32)
        self._tindex = np.zeros(W, np.int32)
        self._seeds = np.zeros(W, np.int32)
        self._tokens = jnp.zeros((W, 1), jnp.int32)
        self._cache = init_policy_cache(cfg, W, max_len)
        self._prefill_fns: Dict[int, Any] = {}  # prompt length -> jitted fn
        # device-side token ring log: step g writes its (W,) sampled tokens
        # to row g % max_len, so the decode loop never materializes (or
        # even lazily indexes) per-token scalars — a request's tokens are
        # harvested from its slot's column in ONE slice at retire. A
        # request spans at most max_len - 1 consecutive steps (its decode
        # headroom), so its rows cannot be overwritten before harvest.
        self._log = jnp.zeros((max_len, W), jnp.int32)
        self._glob = 0  # global decode-step counter (host int)
        self._g0 = np.zeros(W, np.int64)  # per-slot _glob at admission
        self._tok0: List[Any] = [None] * W  # per-slot lazy (1,) prefill tok

        def _step(params, cache, tokens, pos, seeds, tindex, log, row):
            logits, _value, cache = policy_decode(params, cfg, cache,
                                                  tokens, pos)
            # per-request RNG streams: token t of stream `seed` is sampled
            # with fold_in(PRNGKey(seed), t) — no batch-shared key anywhere
            keys = jax.vmap(
                lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
            )(seeds, tindex)
            toks = jax.vmap(jax.random.categorical)(keys, logits)
            toks = toks.astype(jnp.int32)
            log = jax.lax.dynamic_update_slice(
                log, toks[None, :], (row, jnp.int32(0)))
            return toks, cache, log

        self._step_fn = jax.jit(_step, donate_argnums=(1, 6))

        def _place(cache, tokens, small, tok0, slot):
            def scatter(big, one):
                # the batch axis is the unique axis where the 1-row prefill
                # cache differs from the W-row big cache (leaf layouts put
                # it at different depths per family)
                axis = next((i for i, (a, b)
                             in enumerate(zip(big.shape, one.shape))
                             if a != b), None)
                if axis is None:  # max_slots == 1: the row is the cache
                    return one.astype(big.dtype)
                starts = [0] * big.ndim
                starts[axis] = slot
                return jax.lax.dynamic_update_slice(
                    big, one.astype(big.dtype), tuple(starts))

            cache = jax.tree_util.tree_map(scatter, cache, small)
            tokens = jax.lax.dynamic_update_slice(
                tokens, tok0[:, None], (slot, jnp.int32(0)))
            return cache, tokens

        self._place_fn = jax.jit(_place, donate_argnums=(0, 1))

    # -- admission -----------------------------------------------------------
    def _prefill_for(self, length: int):
        fn = self._prefill_fns.get(length)
        if fn is None:
            cfg, max_len = self.cfg, self.max_len

            def _pf(params, tokens, seed):
                logits, _values, cache = policy_prefill(
                    params, cfg, tokens, None, max_len=max_len)
                key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
                tok0 = jax.random.categorical(key, logits[:, -1])
                return tok0.astype(jnp.int32), cache

            fn = jax.jit(_pf)
            self._prefill_fns[length] = fn
        return fn

    def admit(self, slot: int, prompt: np.ndarray, seed: int) -> None:
        """Prefill ``prompt`` into cache row ``slot``. The first sampled
        token (stream index t=0) stays on device until ``harvest``."""
        prompt = np.asarray(prompt, np.int32)
        S = int(prompt.shape[0])
        if S + 1 > self.max_len:
            raise ValueError(
                f"prompt length {S} leaves no decode headroom in a "
                f"max_len={self.max_len} cache")
        tok0, small = self._prefill_for(S)(self.params, prompt[None, :],
                                           seed)
        self._cache, self._tokens = self._place_fn(
            self._cache, self._tokens, small, tok0, slot)
        self._pos[slot] = S
        self._tindex[slot] = 1
        self._seeds[slot] = seed
        self._g0[slot] = self._glob
        self._tok0[slot] = tok0

    # -- decode --------------------------------------------------------------
    # hot-path
    def step(self) -> None:
        """One fixed-width decode step over every slot (leased or idle).
        Tokens land in the device-side ring log; nothing returns to host."""
        row = self._glob % self.max_len
        # copies: the dispatch is asynchronous and may read a host array
        # in place after the bookkeeping below (or the next admit) has
        # already mutated it
        toks, self._cache, self._log = self._step_fn(
            self.params, self._cache, self._tokens, self._pos.copy(),
            self._seeds.copy(), self._tindex.copy(), self._log, row)
        self._tokens = toks[:, None]
        self._pos += 1
        self._tindex += 1
        self._glob += 1

    def remaining(self, slot: int) -> int:
        """Decode headroom before the cache row overflows max_len."""
        return self.max_len - int(self._pos[slot])

    def harvest(self, slot: int, n: int) -> np.ndarray:
        """The first ``n`` tokens sampled for the request resident in
        ``slot`` — one column slice + one host transfer, at retire (off
        the decode hot path)."""
        if n < 1:
            return np.zeros(0, np.int32)
        tok0 = np.asarray(self._tok0[slot], np.int32)  # (1,)
        if n == 1:
            return tok0
        col = np.asarray(self._log[:, slot], np.int32)  # (max_len,)
        rows = (self._g0[slot] + np.arange(n - 1)) % self.max_len
        return np.concatenate([tok0, col[rows]])

    def release(self, slot: int) -> None:
        """Reset host bookkeeping for a freed slot. The device rows are
        *not* zeroed — stale cache contents are masked out by
        construction (see module docstring) and stale log rows are
        overwritten before any future harvest can read them; the next
        admit overwrites the rest."""
        self._pos[slot] = 0
        self._tindex[slot] = 0
        self._seeds[slot] = 0
        self._g0[slot] = 0
        self._tok0[slot] = None
