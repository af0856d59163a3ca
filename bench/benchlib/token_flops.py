"""Model FLOPs of a DeepSeek-V2 token policy, counted from a configuration
file's widths (``bench/configs/<config>.json``, family ``paac_token``).

A multiply-add counts two FLOPs. Causal attention counts half of its
score and value products (the masked half is not work). The held routed
experts count at their expected load: k x held / total experts per token
(top-6 of 64 over 8 held is 0.75 expert-passes a token). Norms, softmaxes,
the rotary embedding and the router's top-k are not counted. The heads
run on each context's last position only.
"""
from __future__ import annotations


def _swiglu(d: int, ff: int) -> int:
    return 3 * 2 * d * ff


def expert_flops_per_assignment(config: dict) -> int:
    """One token through one routed expert (SwiGLU at the expert width)."""
    return _swiglu(config["hidden_size"], config["moe_intermediate_size"])


def layer_flops_per_token(config: dict, ctx: int) -> dict:
    """Forward FLOPs per token of one MLA, one dense feed-forward and one
    expert layer (router, shared and held experts), at a causal context of
    ``ctx`` tokens."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, rank = config["v_head_dim"], config["kv_lora_rank"]
    mean_keys = ctx / 2
    mla = (2 * d * H * (nope + rope) + 2 * d * (rank + rope)
           + 2 * rank * H * (nope + v)
           + 2 * H * (nope + rope + v) * mean_keys
           + 2 * H * v * d)
    ffn = _swiglu(d, config["intermediate_size"])
    load = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["n_routed_experts_total"])
    moe = (2 * d * config["n_routed_experts_total"]
           + _swiglu(d, config["moe_intermediate_size"]
                     * config["n_shared_experts"])
           + load * expert_flops_per_assignment(config))
    return {"mla": mla, "ffn": ffn, "moe": moe}


def forward_flops_per_context(config: dict) -> float:
    """One forward over one context of the game's ``ctx`` tokens and the
    heads at its last position."""
    ctx = config["game"]["ctx"]
    per = layer_flops_per_token(config, ctx)
    dense = config["first_k_dense_replace"]
    sparse = config["num_hidden_layers"] - dense
    per_token = (config["num_hidden_layers"] * per["mla"] + dense * per["ffn"]
                 + sparse * per["moe"])
    heads = 2 * config["hidden_size"] * (config["vocab_size"] + 1)
    return ctx * per_token + heads


def flops_per_timestep(config: dict, t_max: int) -> float:
    """Model FLOPs one environment timestep requires in PAAC: one acting
    forward over the context (F) and its backward (2F), and one bootstrap
    forward per environment per iteration (F / t_max). The learner's
    second forward over the same contexts under the same parameters
    recomputes the acting forward and does not count, nor does the
    rematerialised forward of its backward."""
    return forward_flops_per_context(config) * (3.0 + 1.0 / t_max)
