"""DeepSeek-V2-Lite as a PAAC token policy: YaRN, the held-expert layer,
the heads on the last position, the held-assignment counter, and that the
other models' compiled programs did not change.

The comparisons with the plain reference (``bench/reference/paac_token``)
are in ``bench/tests/test_bench_paac_token.py``.
"""
import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ParallelRL
from repro.core.agents import PAACAgent, PAACConfig
from repro.core.agents.paac import HELD_LEARNER
from repro.core.framework import RUN_COUNTER_EVENT, init_rl_common
from repro.envs import AtariLike, FrameStack, TokenEnv
from repro.models import init_policy, policy_apply, policy_apply_last
from repro.models.common import rope_for, softmax_scale_for
from repro.models.mlp import mlp_forward
from repro.models import moe
from repro.models.moe import init_moe, moe_forward, moe_held_forward
from repro.optim import constant, make_optimizer


def _tiny(**kw):
    """V2-Lite's layer pattern at CPU widths, in float32."""
    base = dict(d_model=64, num_heads=2, num_kv_heads=2, kv_lora_rank=16,
                qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, dense_d_ff=96,
                moe_d_ff=32, d_ff=32, num_experts=16, experts_held=16,
                num_experts_per_tok=3, num_layers=3, vocab_size=128,
                param_dtype="float32", compute_dtype="float32")
    base.update(kw)
    return get_config("deepseek-v2-lite").replace(**base)


# --- YaRN -------------------------------------------------------------------


def test_yarn_frequencies_and_mscale_are_deepseek_v2s():
    """V2-Lite: rope dim 64, theta 1e4, factor 40 over 4096 positions,
    beta 32/1, mscale = mscale_all_dim = 0.707. The correction range is
    channels 10 to 23: floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10
    and ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23."""
    cfg = get_config("deepseek-v2-lite")
    inv_freq, mscale = rope_for(cfg, 64)
    plain = 1e4 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain * (1 - ramp) + plain / 40 * ramp
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    # by hand: the plain frequency up to channel 10, a 40th of it from 23,
    # channel 16 at 7/13 of 1e-2 and 6/13 of 2.5e-4
    assert float(inv_freq[0]) == 1.0
    np.testing.assert_allclose(inv_freq[16], 0.0055, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[31], 1e4 ** (-31 / 32) / 40,
                               rtol=1e-6)
    # cos/sin scale mscale(0.707) / mscale_all_dim(0.707) = 1
    assert mscale == 1.0
    # softmax scale 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2
    assert softmax_scale_for(cfg, 192) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192), rel=1e-12)
    assert softmax_scale_for(cfg, 192) == pytest.approx(0.1147213868, rel=1e-9)


def test_configs_without_yarn_keep_plain_rope():
    cfg = get_config("deepseek-v2-236b")
    assert rope_for(cfg, 64) == (None, 1.0)
    assert softmax_scale_for(cfg, 192) == 1.0 / math.sqrt(192)


# --- the held-expert layer ----------------------------------------------------


def _moe_params(cfg, key):
    return init_moe(key, cfg, jnp.float32)


def _dense_held(p, cfg, x, first, held):
    """The held experts' part by a dense masked sum (numpy)."""
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = x @ np.asarray(p["router"]["w"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :cfg.num_experts_per_tok]
    out = np.zeros_like(x)
    for e in range(held):
        gate = np.where(top == first + e,
                        np.take_along_axis(probs, top, -1), 0.0).sum(-1)
        wi, wg, wo = (np.asarray(p[n][e], np.float64) for n in ("wi", "wg", "wo"))
        h = x @ wi
        h = h / (1 + np.exp(-h)) * (x @ wg)
        out += gate[:, None] * (h @ wo)
    return out


def _interpret_tpu_kernel(mp):
    """Run the held layer through the megablox ``gmm`` a TPU runs,
    interpreted on the CPU."""
    from jax.experimental.pallas.ops.tpu import megablox

    mp.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    mp.setattr(moe, "_on_tpu", lambda: True)


def test_adversarial_routing_drops_nothing(key):
    """Every token routes to the same 3 experts, all held: the buffer is
    full (T*k held assignments) and every one is computed. The capacity
    path, on the same routing, drops."""
    cfg = _tiny(num_shared_experts=0, experts_held=8, experts_held_from=4)
    p = _moe_params(cfg, key)
    w = np.zeros((cfg.d_model, cfg.num_experts), np.float32)
    w[:, 5], w[:, 6], w[:, 7] = 0.03, 0.02, 0.01
    p["router"]["w"] = jnp.asarray(w)
    x = jnp.abs(jax.random.normal(key, (2, 16, cfg.d_model)))
    y, count = moe_held_forward(p, cfg, x)
    assert int(count) == 2 * 16 * cfg.num_experts_per_tok
    want = _dense_held(p, cfg, x, 4, 8)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model), want,
                               rtol=2e-4, atol=2e-5)
    # the capacity path holds all 16 experts' weights: put the same 8 at
    # 4..11 and zero the rest, so only the drops differ
    full = dict(p)
    for n in ("wi", "wg", "wo"):
        pad = jnp.zeros((cfg.num_experts,) + p[n].shape[1:], p[n].dtype)
        full[n] = pad.at[4:12].set(p[n])
    capped, _ = moe_forward(full, cfg.replace(experts_held=0,
                                              norm_topk_prob=False), x)
    gap = np.abs(np.asarray(capped).reshape(-1, cfg.d_model) - want).max()
    assert gap > 1e-2, "the capacity path dropped nothing"


def test_held_count_is_the_assignments_to_held_experts(key):
    cfg = _tiny(num_shared_experts=1, experts_held=4, experts_held_from=8)
    p = _moe_params(cfg, key)
    x = jax.random.normal(key, (3, 8, cfg.d_model))
    _, count = moe_held_forward(p, cfg, x)
    logits = np.asarray(x).reshape(-1, cfg.d_model) @ np.asarray(p["router"]["w"])
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :cfg.num_experts_per_tok]
    assert int(count) == int(((top >= 8) & (top < 12)).sum())


def test_adversarial_routing_drops_nothing_through_the_tpu_kernel(
        key, monkeypatch):
    _interpret_tpu_kernel(monkeypatch)
    test_adversarial_routing_drops_nothing(key)


def test_kernels_agree_forward_and_backward(key):
    """The TPU's megablox kernel (interpreted) and ``lax.ragged_dot`` give
    the layer's output and every gradient alike, on a routing that leaves
    some held experts empty."""
    cfg = _tiny(num_shared_experts=0, experts_held=8, experts_held_from=4)
    p = _moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, cfg.d_model))

    def loss(p, x):
        y, _ = moe_held_forward(p, cfg, x)
        return jnp.sum(jnp.square(y)), y

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    (_, y_rd), g_rd = grad(p, x)
    with pytest.MonkeyPatch.context() as mp:
        _interpret_tpu_kernel(mp)
        (_, y_gmm), g_gmm = grad(p, x)
    np.testing.assert_allclose(y_gmm, y_rd, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_gmm),
                    jax.tree_util.tree_leaves(g_rd)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_shares_of_a_layer_sum_to_the_whole_layer(key):
    """Eight chips, two experts each: the shares' outputs, with the shared
    experts (whole on every chip) counted once, add up to the layer with
    all sixteen experts held."""
    cfg = _tiny(num_shared_experts=2)
    p = _moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, cfg.d_model))
    whole, n_whole = moe_held_forward(p, cfg, x)
    total, n_total = 0.0, 0
    for shard in range(8):
        lo = 2 * shard
        share = cfg.replace(experts_held=2, experts_held_from=lo)
        sp = dict(p, **{n: p[n][lo:lo + 2] for n in ("wi", "wg", "wo")})
        y, n = moe_held_forward(sp, share, x)
        total, n_total = total + y, n_total + int(n)
    shared = mlp_forward(p["shared"], x)
    np.testing.assert_allclose(total - 7 * shared, whole, rtol=1e-4, atol=1e-5)
    assert n_total == int(n_whole) == 2 * 12 * cfg.num_experts_per_tok


# --- heads on the last position -----------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "qwen2-7b"])
def test_last_position_heads_are_the_full_passs_last_row(arch, key):
    cfg = get_config(arch).reduced()
    params = init_policy(key, cfg)
    tokens = jax.random.randint(key, (3, 12), 0, cfg.vocab_size)
    logits, values, _ = policy_apply(params, cfg, tokens)
    last_logits, last_values, _ = policy_apply_last(params, cfg, tokens)
    np.testing.assert_allclose(last_logits, logits[:, -1], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(last_values, values[:, -1], rtol=1e-6,
                               atol=1e-6)


def test_token_agent_acts_on_the_last_position(key):
    cfg = _tiny()
    agent = PAACAgent(cfg)
    params = init_policy(key, cfg)
    tokens = jax.random.randint(key, (4, 10), 0, cfg.vocab_size)
    logits, values = agent.act_fn()(params, tokens)
    assert logits.shape == (4, cfg.vocab_size) and values.shape == (4,)


# --- through ParallelRL ---------------------------------------------------------


def test_held_policy_trains_and_publishes_its_counters():
    cfg = _tiny(experts_held=4, experts_held_from=4, num_layers=3,
                vocab_size=32)
    env = TokenEnv(4, vocab=cfg.vocab_size, ctx=12, k=2, horizon=6)
    seen = {}

    def listener(event, value, **_):
        if event.startswith(RUN_COUNTER_EVENT):
            seen[event] = value

    jax.monitoring.register_scalar_listener(listener)
    try:
        rl = ParallelRL(env, PAACAgent(cfg, PAACConfig(t_max=3)),
                        lr_schedule=constant(0.0028), seed=3)
        res = rl.run(2)
    finally:
        jax.monitoring.unregister_scalar_listener(listener)
    m = res.mean_metrics
    assert math.isfinite(m["loss"])
    # 4 experts of 16 held, 3 of 16 chosen a token: about 3/16 of the
    # learner pass's 2 layers x 12 contexts x 12 tokens x 3 assignments
    assert 0 < m[HELD_LEARNER] <= 2 * 12 * 12 * 3
    assert seen == {RUN_COUNTER_EVENT + HELD_LEARNER:
                    pytest.approx(2 * m[HELD_LEARNER])}


def test_learner_count_is_the_acting_passes_count(key):
    """The learner's pass sees the contexts acting saw, under the same
    parameters: its held count is acting's, which the expert layer's
    roofline reader counts acting's work by."""
    from repro.core.agents.paac import trajectory_logits_values
    from repro.core.rollout import rollout

    cfg = _tiny(experts_held=4, experts_held_from=4, vocab_size=32)
    env = TokenEnv(4, vocab=cfg.vocab_size, ctx=12, k=2, horizon=6)
    params = init_policy(key, cfg)
    state = env.reset(key)
    *_, traj = rollout(PAACAgent(cfg).act_fn(), env, params, state,
                       env.observe(state), key, 3)
    acting = sum(int(policy_apply_last(params, cfg, obs)[2]["moe_held"])
                 for obs in traj.obs)
    _, _, aux = trajectory_logits_values(params, cfg, traj)
    assert int(aux["moe_held"]) == acting > 0


# --- the other models' programs are unchanged ------------------------------------


def _stripped(text: str) -> str:
    """Compiled HLO text without its metadata and source-location tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)", line))


def _renamed(text: str) -> str:
    """Instruction names replaced by their order of first appearance."""
    names = {}

    def sub(m):
        return names.setdefault(m.group(0), f"%v{len(names)}")
    return re.sub(r"%[A-Za-z0-9_.\-]+", sub, text)


def _cnn_train_step_hlo(name: str, n_envs: int) -> str:
    env = FrameStack(AtariLike(n_envs), n=4)
    cfg = get_config(name).replace(obs_shape=env.obs_shape,
                                   num_actions=env.num_actions)
    agent = PAACAgent(cfg, PAACConfig(gamma=0.99, entropy_beta=0.01, t_max=5,
                                      value_coef=0.5))
    lr = constant(0.0007 * n_envs)

    def init():
        *_, key, k_env, params, opt_state = init_rl_common(
            env, agent, "rmsprop", lr, 0)
        state = env.reset(k_env)
        return params, opt_state, state, env.observe(state), key

    shapes = jax.eval_shape(init) + (jax.ShapeDtypeStruct((), jnp.int32),)
    step = jax.jit(agent.make_train_step(env, make_optimizer("rmsprop"), lr))
    return step.lower(*shapes).compile().as_text()


# sha256 of the stripped, renamed optimized CPU HLO of the benchmark's CNN
# cells' train steps, read from the program before the token heads moved
# to the last position. Instruction names are replaced by their order of
# appearance: they follow what else the process compiled before.
CNN_STEP_DIGESTS = {
    ("paac_nature", 32):
        "a3d4b4a47900cbb777b518716de3393bc5efd97a6561c2dd94b669879e09d4e8",
    ("paac_nature", 256):
        "d3d2e047480541741134450cb39d6d27c11155b3e910e04631de5bb84bb7ab01",
    ("paac_nips", 32):
        "fc8a8d43aa56ee8a55874ee931109cd0a8810340c7a91d5d27ad29849d9e6fec",
}
# sha256 of the stripped, renamed CPU HLO of a YaRN-less MLA policy's
# forward (deepseek-v2-236b reduced), read from the program before YaRN
MLA_FORWARD_DIGEST = (
    "0318cc83c2284cbd8d736eaa13ab996522fe4678d45292b2259f941b5f36a509")


@pytest.mark.parametrize("name, n_envs", sorted(CNN_STEP_DIGESTS))
def test_cnn_cells_train_step_hlo_is_unchanged(name, n_envs):
    text = _renamed(_stripped(_cnn_train_step_hlo(name, n_envs)))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        CNN_STEP_DIGESTS[(name, n_envs)]


def test_mla_without_yarn_compiles_as_before():
    cfg = get_config("deepseek-v2-236b").reduced()
    params = jax.eval_shape(lambda: init_policy(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = jax.jit(lambda p, t: policy_apply(p, cfg, t)[:2]).lower(
        params, tokens).compile().as_text()
    assert hashlib.sha256(_renamed(_stripped(text)).encode()).hexdigest() == \
        MLA_FORWARD_DIGEST
