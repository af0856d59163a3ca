"""Production mesh construction.

Target: TPU v5e, 256 chips per pod. Single pod: (data=16, model=16).
Multi-pod: (pod=2, data=16, model=16) = 512 chips — the "pod" axis extends
data parallelism across the ICI/DCN boundary (PAAC's synchronous gradient
all-reduce spans it; see DESIGN.md §5).

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths (constraints become no-ops)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_rollout_mesh(n_devices: int = 0):
    """1-axis ``("data",)`` mesh for the pipeline's mesh rollout plane.

    The RL pipeline (``repro.pipeline``) is pure data parallelism: the env
    axis of every rollout shards over ``"data"`` and the learner's gradients
    all-reduce across it, so its mesh has no ``"model"`` axis (the policy
    networks are small; contrast the production inference mesh above).
    ``n_devices=0`` takes every visible device; CI exercises multi-device
    shapes on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set *before* the first jax import — device count is fixed at init).
    """
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(
            f"mesh_shape={n} but only {len(devices)} device(s) visible — on "
            "CPU, export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the first jax import"
        )
    # Auto axes: the learner leaves partitioning (and the gradient
    # all-reduce) to XLA's SPMD partitioner. JAX's default Explicit axes
    # would put the sharding into every array's type and refuse the
    # learner's (T, E) -> (T*E,) batch flatten over the sharded env axis.
    return jax.make_mesh((n,), ("data",), devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))


# Hardware constants for the roofline (TPU v5e)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
