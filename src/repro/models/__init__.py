"""Unified policy/value model API (paper §3: the framework is model-agnostic).

Every backbone — the paper's CNNs and all ten assigned architectures —
exposes the same functional surface consumed by the PAAC core and launchers:

* ``init_policy(key, cfg)``                    -> params
* ``policy_apply(params, cfg, obs/tokens, …)`` -> (logits, values) full pass
* ``policy_apply_last(params, cfg, tokens, …)`` -> the heads at the last
  position only (a token policy's action and value)
* ``init_policy_cache(cfg, batch, max_len)``   -> decode cache (token models)
* ``policy_decode(params, cfg, cache, tok, pos)`` -> (logits, value, cache)
* ``policy_prefill(params, cfg, tokens, …)``   -> (logits, value, cache)
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.convnet import cnn_forward, init_cnn
from repro.models.heads import apply_heads, init_heads
from repro.models.common import split_keys


def init_policy(key, cfg):
    ks = split_keys(key, 2)
    if cfg.family == "cnn":
        trunk = init_cnn(ks[0], cfg)
    else:
        trunk = tfm.init_model(ks[0], cfg)
    return {"trunk": trunk, "heads": init_heads(ks[1], cfg)}


def policy_apply(params, cfg, obs, prefix_embeds=None, *, train: bool = False,
                 window: Optional[int] = None):
    """Full batched evaluation.

    CNN family: obs (B, ...) -> (logits (B,A), values (B,)).
    Token families: obs = tokens (B,S) -> per-position (logits (B,S,A),
    values (B,S)) plus aux dict.
    """
    if cfg.family == "cnn":
        h = cnn_forward(params["trunk"], cfg, obs)
        logits, value = apply_heads(params["heads"], cfg, h)
        return logits, value, {}
    hidden, aux = tfm.forward(params["trunk"], cfg, obs, prefix_embeds,
                              train=train, window=window)
    embed = params["trunk"]["embed"] if cfg.tie_policy_head else None
    logits, values = apply_heads(params["heads"], cfg, hidden, embed)
    return logits, values, aux


def policy_apply_last(params, cfg, tokens, *, train: bool = False):
    """Token policies: the trunk over the whole context, the heads on its
    last position only. tokens (B, S) -> (logits (B, A), values (B,), aux)
    — the last row of ``policy_apply``'s per-position outputs, without the
    other S-1 rows' policy logits."""
    hidden, aux = tfm.forward(params["trunk"], cfg, tokens, train=train)
    embed = params["trunk"]["embed"] if cfg.tie_policy_head else None
    logits, values = apply_heads(params["heads"], cfg, hidden[:, -1], embed)
    return logits, values, aux


def init_policy_cache(cfg, batch: int, max_len: int, dtype=None):
    return tfm.init_cache(cfg, batch, max_len, dtype)


def policy_decode(params, cfg, cache, token, pos, *, window: Optional[int] = None):
    """One decode step: token (B,1) -> (logits (B,A), value (B,), cache)."""
    hidden, cache = tfm.decode_step(params["trunk"], cfg, cache, token, pos, window=window)
    embed = params["trunk"]["embed"] if cfg.tie_policy_head else None
    logits, value = apply_heads(params["heads"], cfg, hidden, embed)
    return logits[:, 0], value[:, 0], cache


def policy_prefill(params, cfg, tokens, prefix_embeds=None, *,
                   window: Optional[int] = None, max_len: Optional[int] = None):
    hidden, cache = tfm.prefill(params["trunk"], cfg, tokens, prefix_embeds,
                                window=window, max_len=max_len)
    embed = params["trunk"]["embed"] if cfg.tie_policy_head else None
    logits, values = apply_heads(params["heads"], cfg, hidden, embed)
    return logits, values, cache
