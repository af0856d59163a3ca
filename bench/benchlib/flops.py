"""Model FLOPs of the PAAC networks, counted from a configuration's widths.

A multiply-add counts two FLOPs. Biases, ReLUs, the softmax and the game's
own arithmetic are not counted: they are a rounding error beside the
convolutions and the dense layer.
"""
from __future__ import annotations


def forward_flops_per_frame(config: dict) -> int:
    """FLOPs of one forward pass over one (H, W, C) observation."""
    size, _, ch = config["obs_shape"]
    total = 0
    for feat, kern, stride in config["convs"]:
        size = (size - kern) // stride + 1
        total += 2 * size * size * feat * kern * kern * ch
        ch = feat
    flat = size * size * ch
    total += 2 * flat * config["dense"]
    # policy logits and the one value output
    total += 2 * config["dense"] * (config["num_actions"] + 1)
    return total


def flops_per_timestep(config: dict, t_max: int) -> float:
    """Model FLOPs that one environment timestep requires in PAAC.

    One acting forward (F) and one backward (2F) per timestep, and one
    bootstrap forward per environment per iteration (F / t_max). The
    learner's second forward over the same frames under the same parameters
    recomputes the acting forward and does not count.
    """
    return forward_flops_per_frame(config) * (3.0 + 1.0 / t_max)
