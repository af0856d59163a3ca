"""Sharding-rule validation against the production mesh (AbstractMesh — no
device allocation, so smoke tests still see 1 real device)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed.sharding import cache_specs, input_sharding, param_specs
from repro.models import init_policy, init_policy_cache

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _params_sds(cfg):
    return jax.eval_shape(lambda: init_policy(jax.random.PRNGKey(0), cfg))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("mode", ["tp", "fsdp_tp"])
def test_param_specs_divisible(arch, mesh, mode):
    """Every sharded dim divides its mesh axis (no silent padding)."""
    cfg = get_config(arch)
    sds = _params_sds(cfg)
    specs = param_specs(sds, mesh, mode)
    sizes = dict(mesh.shape)

    def axis_size(a):
        if a is None:
            return 1
        if isinstance(a, tuple):
            n = 1
            for x in a:
                n *= sizes[x]
            return n
        return sizes[a]

    flat_s = jax.tree_util.tree_flatten_with_path(specs)[0]
    flat_l = {tuple(p): l for p, l in jax.tree_util.tree_flatten_with_path(sds)[0]}
    n_sharded = 0
    for path, spec in flat_s:
        leaf = flat_l[tuple(path)]
        assert len(spec) <= leaf.ndim
        for dim, a in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
            s = axis_size(a)
            if s > 1:
                n_sharded += 1
                assert dim % s == 0, (path, leaf.shape, spec)
    assert n_sharded > 0  # something actually shards


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "dbrx-132b",
                                  "deepseek-coder-33b"])
def test_fsdp_bounds_per_chip_param_bytes(arch):
    """fsdp_tp must fit params+opt-state in HBM: <= 6 GB/chip param bytes
    (leaving room for fp32 RMSProp stats + activations on a 16 GB v5e)."""
    cfg = get_config(arch)
    sds = _params_sds(cfg)
    specs = param_specs(sds, MESH, "fsdp_tp")
    sizes = dict(MESH.shape)

    def axis_size(a):
        if a is None:
            return 1
        if isinstance(a, tuple):
            n = 1
            for x in a:
                n *= sizes[x]
            return n
        return sizes[a]

    per_chip = 0
    for (path, spec), (_, leaf) in zip(
        jax.tree_util.tree_flatten_with_path(specs)[0],
        jax.tree_util.tree_flatten_with_path(sds)[0],
    ):
        shard_elems = leaf.size
        for dim, a in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
            shard_elems //= axis_size(a) if dim % axis_size(a) == 0 else 1
        per_chip += shard_elems * leaf.dtype.itemsize
    assert per_chip < 6e9, f"{per_chip/1e9:.2f} GB/chip"


def test_moe_experts_shard_over_model():
    cfg = get_config("dbrx-132b")
    sds = _params_sds(cfg)
    specs = param_specs(sds, MESH, "fsdp_tp")
    moe_spec = specs["trunk"]["layers"]["moe"]["wi"]
    # the data dim may be a bare axis name or a (possibly multi-)axis tuple
    assert tuple(moe_spec) in (
        (None, "model", "data", None),
        (None, "model", ("data",), None),
    )


def test_cache_specs_batch_and_heads():
    cfg = get_config("deepseek-coder-33b")
    cache = jax.eval_shape(lambda: init_policy_cache(cfg, 128, 1024))
    specs = cache_specs(cache, MESH)
    k_spec = specs["layers"]["attn"]["k"]  # (L, B, S, Hkv, D)
    assert k_spec[1] in ("data", ("data",))
    # kv=8 heads do not divide model=16 -> unsharded
    assert k_spec[3] is None


def test_input_sharding_batch_only_when_divisible():
    batch = {
        "tokens": jax.ShapeDtypeStruct((256, 4097), jnp.int32),
        "one": jax.ShapeDtypeStruct((1, 5), jnp.float32),
    }
    sh = input_sharding(batch, MESH)
    assert sh["tokens"][0] in ("data", ("data",))
    assert sh["one"] == P(None, None)


def test_multipod_data_axes():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4097), jnp.int32)}
    sh = input_sharding(batch, MESH_MP)
    assert sh["tokens"][0] == ("pod", "data")
