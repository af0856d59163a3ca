"""The ``paac_token`` family: DeepSeek-V2-Lite's configuration, its FLOP
count, its plain reference against the program, and the readers of its
expert and attention layers.

The program runs a tiny configuration of the same layer pattern
(``data/paac_token_tiny``, float32) on the CPU: three PAAC updates through
``ParallelRL`` on ``TokenEnv`` agree with the reference to float32
round-off, and each planted fault and the float8 control fail the tiny
cell's limits.
"""
import math
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import cells, counters, harness, model_scopes, scopes, token_flops
from benchlib import trace as tr
from reference import paac_token as ref

SEED = 2**31 + 29
TINY = Path(__file__).with_name("data") / "paac_token_tiny"
TINY_CELL = "dsv2-tiny-e4"
TINY_ARCH = "dsv2-lite-tiny"


def _tiny_arch():
    from repro.configs import get_config

    return get_config("deepseek-v2-lite").replace(
        d_model=64, num_heads=2, num_kv_heads=2, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, dense_d_ff=96,
        moe_d_ff=32, d_ff=32, num_experts=16, num_experts_per_tok=3,
        param_dtype="float32", compute_dtype="float32")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of ``bench/`` with the tiny cell's files, the tiny
    architecture registered, and one run of the cell on a CPU device."""
    from repro.configs import base

    copy = tmp_path_factory.mktemp("checkout") / "bench"
    shutil.copytree(cells.BENCH, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src in TINY.rglob("*.json"):
        shutil.copy(src, copy / src.relative_to(TINY))
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(base._REGISTRY, TINY_ARCH, _tiny_arch)
        mp.setattr(cells, "BENCH", copy)
        mp.setattr(cells, "ROOT", copy.parent)
        cell = cells.load_cell(TINY_CELL)
        result = harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                                  time.perf_counter(),
                                  ["timesteps_per_s", "setup_s"])
        planted = {
            f: harness.reference_readings(
                cell, SEED, harness.reference_as_program(cell, SEED, fault=f))
            for f in cell.family.FAULTS}
        planted["control"] = harness.reference_readings(
            cell, SEED, harness.reference_as_program(cell, SEED,
                                                     dtype=jnp.bfloat16))
        yield SimpleNamespace(cell=cell, result=result, planted=planted)


# --- the configuration ---------------------------------------------------------


def test_configuration_is_the_catalog_row_cut_to_one_chips_share():
    cell = cells.load_cell("dsv2lite-sync-e8-c512")
    config = cell.config
    assert config["family"] == "paac_token"
    assert sorted(config["reduced"]) == sorted(config["published"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 27,
                                   "n_routed_experts": 64,
                                   "vocab_size": 102400}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["n_routed_experts_total"]) == \
        (5, 8, 12800, 64)
    # every width as published
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_attention_heads"],
            config["num_experts_per_tok"], config["n_shared_experts"]) == \
        (2048, 10944, 1408, 512, 128, 64, 128, 16, 6, 2)
    assert config["rope_scaling"]["type"] == "yarn"
    assert config["norm_topk_prob"] is False


def test_job_runs_what_the_file_states():
    cell = cells.load_cell("dsv2lite-sync-e8-c512")
    env, agent, settings = cell.family.job(cell.config, 8, 5)
    harness.guard_widths(settings, cell.config)
    assert (env.n_envs, env.vocab, env.ctx, env.k, env.horizon) == \
        (8, 12800, 512, 2, 64)
    assert agent.cfg.experts_held == 8 and agent.cfg.num_experts == 64
    harness.check_lr_rule(cell.workload, cell.config)


def test_flops_are_the_hand_count():
    """Per token of a 512-token causal context: MLA 30.22 MFLOP (q 12.58M,
    kv-down 2.36M, kv-up 4.19M, attention 2.62M at 256 mean keys, out
    8.39M), dense SwiGLU 134.5M, expert layer 47.9M (router 0.26M, shared
    34.6M, 0.75 held experts 13.0M)."""
    config = cells.load_cell("dsv2lite-sync-e8-c512").config
    per = token_flops.layer_flops_per_token(config, 512)
    assert per["mla"] == 2 * 2048 * 3072 + 2 * 2048 * 576 + 2 * 512 * 4096 \
        + 2 * 16 * 320 * 256 + 2 * 2048 * 2048
    assert per["ffn"] == 6 * 2048 * 10944
    assert token_flops.expert_flops_per_assignment(config) == 6 * 2048 * 1408
    assert per["moe"] == 2 * 2048 * 64 + 6 * 2048 * 2816 \
        + 0.75 * 6 * 2048 * 1408
    per_token = 5 * per["mla"] + per["ffn"] + 4 * per["moe"]
    assert per_token == pytest.approx(0.477e9, rel=0.01)
    context = 512 * per_token + 2 * 2048 * 12801
    assert token_flops.forward_flops_per_context(config) == context
    assert cells.load_cell("dsv2lite-sync-e8-c512").family.flops_per_timestep(
        config, 5) == context * 3.2


# --- program against reference ----------------------------------------------------


def test_tiny_cell_three_updates_are_correct(tiny):
    result = tiny.result
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    for name, v in result["check"].items():
        assert v["value"] < 1e-4, (name, v)


@pytest.mark.parametrize("variant", ref.FAULTS + ("control",))
def test_each_planted_fault_and_the_float8_control_fail(tiny, variant):
    readings = tiny.planted[variant]
    limits = tiny.cell.workload["limits"]
    assert any(readings[k] > limits[k] for k in limits), readings


def test_reference_stores_each_update_as_the_configuration_does(tiny):
    config = dict(tiny.cell.config, param_dtype="bfloat16")
    run = ref.train(config, SEED, n_envs=2, lanes=1, t_max=2, lr=0.0014,
                    steps=1)
    moved = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(run["params"]):
        name = jax.tree_util.keystr(path)
        as_bf16 = np.asarray(jnp.asarray(leaf).astype(jnp.bfloat16),
                             np.float32)
        if "router" in name:
            assert not np.array_equal(leaf, as_bf16), name
        else:
            np.testing.assert_array_equal(leaf, as_bf16, err_msg=name)
    for before, after in zip(jax.tree_util.tree_leaves(run["params0"]),
                             jax.tree_util.tree_leaves(run["params"])):
        moved += int(np.any(before != after))
    assert moved > 0


def test_model_logits_and_values_match_the_reference(tiny):
    from repro.models import init_policy, policy_apply_last

    cfg = tiny.cell.family.job(tiny.cell.config, 2, 3)[1].cfg
    net = ref.Net.from_config(tiny.cell.config)
    key = jax.random.PRNGKey(7)
    params = init_policy(key, cfg)
    tokens = jax.random.randint(key, (3, 16), 0, cfg.vocab_size)
    logits, values, _ = policy_apply_last(params, cfg, tokens)
    r_logits, r_values = ref.forward(ref.init_params(key, net), net, tokens,
                                     ref.variant_flags())
    np.testing.assert_allclose(logits, r_logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(values, r_values, rtol=2e-4, atol=2e-5)


def test_shares_sum_to_the_uncut_reference_layer(tiny):
    """Four chips of four experts each over the tiny layer's 16: the
    program's shares, shared experts counted once, add up to the
    reference's layer with every expert held."""
    from repro.models.mlp import mlp_forward
    from repro.models.moe import init_moe, moe_held_forward

    cfg = tiny.cell.family.job(tiny.cell.config, 2, 3)[1].cfg.replace(
        experts_held=16, experts_held_from=0)
    key = jax.random.PRNGKey(3)
    p = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    total = 0.0
    for shard in range(4):
        lo = 4 * shard
        share = cfg.replace(experts_held=4, experts_held_from=lo)
        sp = dict(p, **{n: p[n][lo:lo + 4] for n in ("wi", "wg", "wo")})
        total = total + moe_held_forward(sp, share, x)[0]
    total = total - 3 * mlp_forward(p["shared"], x)
    uncut = ref.Net.from_config(dict(tiny.cell.config, n_routed_experts=16,
                                     experts_held_from=0))
    want = ref.moe(p, uncut, x, ref._mm(ref.variant_flags()),
                   ref.variant_flags())
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_reference_rope_is_the_programs(tiny):
    from repro.models.common import rope_for

    net = ref.Net.from_config(cells.load_cell("dsv2lite-sync-e8-c512").config)
    cos, sin = ref.rope_tables(net, jnp.arange(5))
    cfg = cells.load_cell("dsv2lite-sync-e8-c512").family.job(
        cells.load_cell("dsv2lite-sync-e8-c512").config, 1, 5)[1].cfg
    inv_freq, mscale = rope_for(cfg, 64)
    angles = jnp.arange(5, dtype=jnp.float32)[:, None] * inv_freq
    np.testing.assert_allclose(cos, jnp.cos(angles) * mscale, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(sin, jnp.sin(angles) * mscale, rtol=1e-5,
                               atol=1e-6)


# --- the readers ---------------------------------------------------------------------


def _ev(name, start, end):
    return tr.Event(name, float(start), float(end))


def _run(events, op_names):
    trace = tr.Trace({0: sorted(events, key=lambda e: e.start)}, [])
    cell = cells.load_cell("dsv2lite-sync-e8-c512")
    return SimpleNamespace(trace=trace, lo=0.0, hi=1e9, updates=2, chips=1,
                           program_probe=SimpleNamespace(op_names=op_names),
                           config=cell.config, workload=cell.workload,
                           device_kind="TPU v5 lite")


OPS = {
    "%fusion.1": "jit(train_step)/rollout/while/body/policy.forward/mla/dot_general",
    "%gmm.3": ("jit(train_step)/transpose(jvp(learner))/loss_grad/while/body/"
               "checkpoint/moe.experts/jit(gmm)/pallas_call"),
    "%fusion.4": "jit(train_step)/learner/loss_grad/while/body/moe.route/sort",
    "%while.5": "jit(train_step)/learner/loss_grad/while",
    "%copy.6": "",
    "%fusion.7": "jit(train_step)/learner/loss_grad/while/body/moe.shared/dot",
    "%fusion.8": "jit(train_step)/learner/optimizer/mul",
}


def test_model_scopes_split_self_time_by_scope():
    events = [_ev("%fusion.1", 0, 100), _ev("%while.5", 100, 1000),
              _ev("%gmm.3", 200, 500), _ev("%fusion.4", 500, 600),
              _ev("%copy.6", 600, 700), _ev("%fusion.7", 700, 900),
              _ev("%fusion.8", 1000, 1100)]
    run = _run(events, OPS)
    split = model_scopes.split_seconds(run)
    assert split["mla"] == pytest.approx(100e-9)
    assert split["moe.experts"] == pytest.approx(300e-9)
    assert split["moe.route"] == pytest.approx(100e-9)
    assert split["moe.shared"] == pytest.approx(200e-9)
    # the copy inside the loop has no scope of its own, nor does the loop
    assert split[None] == pytest.approx((100 + 100 + 100 + 100) * 1e-9)
    assert sum(split.values()) == pytest.approx(1100e-9)
    assert cells.reader("moe_ms_per_update").read(run) == pytest.approx(
        1e3 * 600e-9 / 2)
    assert cells.reader("mla_ms_per_update").read(run) == pytest.approx(
        1e3 * 100e-9 / 2)


def test_model_scopes_read_nothing_for_an_unknown_program():
    events = [_ev("%fusion.1", 0, 100), _ev("%other.9", 100, 300)]
    assert model_scopes.split_seconds(_run(events, OPS)) == {}
    plain = {k: v.replace("mla/", "") for k, v in OPS.items()
             if "moe" not in v}
    assert model_scopes.split_seconds(
        _run([_ev("%fusion.1", 0, 100)], plain)) == {}
    assert scopes.MISMATCH == 0.01


def test_experts_roofline_share_counts_the_programs_assignments(monkeypatch):
    events = [_ev("%gmm.3", 0, 1_000_000)]
    run = _run(events, OPS)
    reader = cells.reader("experts_roofline_share")
    monkeypatch.setattr(counters, "_last", {})
    assert reader.read(run) is None  # a program that publishes no counter
    monkeypatch.setattr(counters, "_last", {"count.moe_held_learner": 1000.0})
    # the learner's pass 3x, acting's forwards 1x, the bootstrap 1/t_max
    flops = 6 * 2048 * 1408 * (4 + 1 / 5) * 1000
    assert reader.read(run) == pytest.approx(
        100 * flops / (1e-3 * 197e12), rel=1e-9)


def test_counters_keep_the_last_runs_totals(monkeypatch):
    monkeypatch.setattr(counters, "_last", {})
    counters.listen()
    jax.monitoring.record_scalar("/repro/run/count.x", 3.0)
    jax.monitoring.record_scalar("/repro/run/count.x", 5.0)
    jax.monitoring.record_scalar("/jax/other", 1.0)
    assert counters.last_run("count.x") == 5.0
    assert counters.last_run("count.y") is None
    assert math.isfinite(counters.last_run("count.x"))
