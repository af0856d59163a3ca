"""Plain PAAC training of a DeepSeek-V2 token policy, written from the
published equations.

Clemente et al. 2017 (arXiv:1705.04862), Algorithm 1, with a DeepSeek-V2
language model (arXiv:2405.04434; the Hugging Face ``deepseek_v2``
modelling code) as the policy and value network, in straightforward
``jax.numpy``:

* the network: token embedding; a stack of pre-norm blocks (RMSNorm,
  then multi-head latent attention, RMSNorm, then a SwiGLU feed-forward
  in the first ``first_k_dense_replace`` blocks and a mixture of experts
  in the rest); a final RMSNorm; a linear policy head over the vocabulary
  and a linear value head, on the context's last position;
* MLA without query compression: q = h W_q split into a 128-wide part and
  a 64-wide rotary part; [c, k_r] = h W_dkv, c RMS-normalised, [k_n, v] =
  c W_ukv per head; k = [k_n, k_r] with k_r shared by the heads; causal
  softmax attention with scale 1/sqrt(192) times YaRN's
  mscale(mscale_all_dim)^2; rotary frequencies by YaRN (plain ones for
  the fast channels, divided by the factor for the slow ones, a linear
  ramp between the correction range of beta_fast and beta_slow over the
  original positions), cosines and sines times mscale(mscale) /
  mscale(mscale_all_dim); channels in halves;
* the expert layer as one chip of the deployment holds it: a float32
  router over all ``n_routed_experts_total`` experts, softmax, greedy
  top-k, weights as they come (``norm_topk_prob`` false) times
  ``routed_scaling_factor``; the output is the sum over the held experts
  of gate x SwiGLU expert, the gate 0 where a token did not route to the
  expert (a dense, masked sum: every held expert runs on every token),
  plus the shared experts' SwiGLU of width n_shared x expert width;
* acting: one batched forward for every environment, an independent
  categorical draw per environment, then the game's step (a copy of the
  repository's ``TokenEnv``: k-back echo over the vocabulary slice);
* n-step returns, the losses of equations (10) and (11) over the
  n_e * t_max samples, global-norm clipping, RMSProp with one shared
  accumulator.

``dtype`` float32 computes every matmul at full float32 precision
(``Precision.HIGHEST``): the oracle. Any other ``dtype`` asks for the
control one precision below the configuration's bfloat16: the same
computation with every matmul operand rounded to ``float8_e4m3fn``.

Weights are drawn from the seed exactly as the system draws them (the same
keys, shapes, scales and leaf names), rounded to the configuration's
parameter dtype, then held in float32; each update's new parameters are
rounded to that dtype again (the router's stay float32), since the
configuration stores them so: an update under half a step of the stored
dtype leaves a parameter where it was. The learner's gradient is summed
over chunks of the batch, each block rematerialised in the backward pass,
and the feed-forward runs in token blocks, so that the reference fits one
chip at the cell's sizes. Nothing here
imports the system under test.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FAULTS = ("capacity_routing", "renormalized_topk", "plain_rope",
          "no_shared_expert", "half_batch")
# the variants of one compiled reference: a 0/1 flag each, passed as data
# so that the oracle, the control and every fault share one program
VARIANTS = ("control",) + FAULTS
# the program's capacity path: drop an expert's assignments past
# ceil(tokens * k * factor / experts) per routing group (one sequence)
CAPACITY_FACTOR = 1.25
# tokens per block of the feed-forward and expert sums
FFN_BLOCK = 4096
# contexts per chunk of the learner's batch (gradients summed over chunks)
LEARN_CHUNK = 8


class Net(NamedTuple):
    """The widths of one configuration file, hashable for ``jax.jit``."""
    layers: int
    dense_layers: int
    d: int
    vocab: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    dense_ff: int
    expert_ff: int
    experts_total: int
    held: int
    held_from: int
    k: int
    shared: int
    norm_topk: bool
    route_scale: float
    eps: float
    theta: float
    yarn: tuple  # (factor, original_max_pos, beta_fast, beta_slow, mscale, mscale_all_dim)
    param_dtype: str

    @classmethod
    def from_config(cls, config: dict) -> "Net":
        y = config["rope_scaling"]
        return cls(
            config["num_hidden_layers"], config["first_k_dense_replace"],
            config["hidden_size"], config["vocab_size"],
            config["num_attention_heads"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"],
            config["n_routed_experts_total"], config["n_routed_experts"],
            config["experts_held_from"], config["num_experts_per_tok"],
            config["n_shared_experts"], bool(config["norm_topk_prob"]),
            float(config["routed_scaling_factor"]),
            float(config["rms_norm_eps"]), float(config["rope_theta"]),
            (float(y["factor"]), int(y["original_max_position_embeddings"]),
             float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
             float(y["mscale_all_dim"])),
            config["param_dtype"])


class Hyper(NamedTuple):
    gamma: float
    beta: float
    value_coef: float
    decay: float
    eps: float
    clip: float
    lr: float

    @classmethod
    def from_config(cls, config: dict, lr: float) -> "Hyper":
        opt = config["optimizer"]
        return cls(config["gamma"], config["entropy_beta"],
                   config["value_coef"], opt["decay"], opt["eps"],
                   opt["clip_norm"], lr)


class Game(NamedTuple):
    """The k-back echo game over ``vocab`` ids, ``ctx`` tokens of history,
    episodes of ``horizon`` steps; ``n`` instances."""
    n: int
    vocab: int
    ctx: int
    k: int
    horizon: int

    def _reset_one(self, key):
        return {"hist": jax.random.randint(key, (self.ctx,), 0, self.vocab),
                "t": jnp.zeros((), jnp.int32)}

    def reset(self, key):
        return jax.vmap(self._reset_one)(jax.random.split(key, self.n))

    @staticmethod
    def observe(state):
        return state["hist"]

    def step(self, state, actions, key):
        ks = jax.random.split(key, 2 * self.n).reshape(2, self.n, -1)
        target = state["hist"][:, -self.k]
        reward = (actions == target).astype(jnp.float32)
        hist = jnp.concatenate([state["hist"][:, 1:],
                                actions[:, None].astype(jnp.int32)], axis=1)
        t = state["t"] + 1
        done = t >= self.horizon
        fresh = jax.vmap(self._reset_one)(ks[1])
        new = {"hist": jnp.where(done[:, None], fresh["hist"], hist),
               "t": jnp.where(done, fresh["t"], t)}
        return new, reward, done


# ---------------------------------------------------------------------------
# Weights, drawn as the system draws them
# ---------------------------------------------------------------------------


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape) * std).astype(dtype)


def _linear(key, din, dout, dtype, scale=1.0):
    return {"w": _normal(key, (din, dout), scale / math.sqrt(din), dtype)}


def _swiglu(key, d, ff, dtype):
    ks = jax.random.split(key, 3)
    return {"wi": _linear(ks[0], d, ff, dtype), "wg": _linear(ks[1], d, ff, dtype),
            "wo": _linear(ks[2], ff, d, dtype)}


def _block(key, net: Net, dtype, dense: bool):
    ks = jax.random.split(key, 6)
    a = jax.random.split(ks[0], 6)
    qk = net.nope + net.rope
    p = {"ln1": {"scale": jnp.ones((net.d,), dtype)},
         "attn": {"wq": _linear(a[0], net.d, net.heads * qk, dtype),
                  "wdkv": _linear(a[2], net.d, net.kv_rank + net.rope, dtype),
                  "kv_norm": {"scale": jnp.ones((net.kv_rank,), dtype)},
                  "wukv": _linear(a[3], net.kv_rank,
                                  net.heads * (net.nope + net.v), dtype),
                  "wo": _linear(a[4], net.heads * net.v, net.d, dtype,
                                scale=1.0 / math.sqrt(2 * net.layers))},
         "ln2": {"scale": jnp.ones((net.d,), dtype)}}
    if dense:
        p["mlp"] = _swiglu(ks[2], net.d, net.dense_ff, dtype)
    else:
        m = jax.random.split(ks[3], 5)
        d, ff, E = net.d, net.expert_ff, net.held
        p["moe"] = {
            "router": _linear(m[0], d, net.experts_total, jnp.float32),
            "wi": (jax.random.normal(m[1], (E, d, ff)) / math.sqrt(d)).astype(dtype),
            "wg": (jax.random.normal(m[2], (E, d, ff)) / math.sqrt(d)).astype(dtype),
            "wo": (jax.random.normal(m[3], (E, ff, d)) / math.sqrt(ff)).astype(dtype),
            "shared": _swiglu(m[4], d, ff * net.shared, dtype)}
    return p


def stored_params(key, net: Net) -> Dict:
    """The system's parameters for ``key``, in the dtypes it stores them:
    the configuration's parameter dtype, the router float32."""
    dtype = jnp.dtype(net.param_dtype)
    k_trunk, k_heads = jax.random.split(key)
    ks = jax.random.split(k_trunk, 10)
    trunk = {"embed": _normal(ks[0], (net.vocab, net.d), 0.02, dtype)}
    if net.dense_layers:
        trunk["first"] = jax.vmap(lambda k: _block(k, net, dtype, True))(
            jax.random.split(ks[4], net.dense_layers))
    trunk["layers"] = jax.vmap(lambda k: _block(k, net, dtype, False))(
        jax.random.split(ks[1], net.layers - net.dense_layers))
    trunk["final_norm"] = {"scale": jnp.ones((net.d,), dtype)}
    kh = jax.random.split(k_heads, 2)
    value = _linear(kh[1], net.d, 1, dtype)
    value["b"] = jnp.zeros((1,), dtype)
    heads = {"value": value, "policy": _linear(kh[0], net.d, net.vocab, dtype)}
    return {"trunk": trunk, "heads": heads}


def init_params(key, net: Net) -> Dict:
    """The system's parameters for ``key``, as float32 arrays."""
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  stored_params(key, net))


def _round_to(x, dtype):
    """x rounded to ``dtype``'s precision, held in float32. An
    ``astype`` round trip would not do: XLA may carry the value through a
    float32-bfloat16-float32 pair of converts at float32 (excess
    precision), as it does on a TPU."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return lax.reduce_precision(x, exponent_bits=info.nexp,
                                mantissa_bits=info.nmant)


def store(params, net: Net) -> Dict:
    """Each float32 parameter rounded to the dtype the system stores it in,
    held in float32."""
    stored = jax.eval_shape(partial(stored_params, net=net),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(lambda p, s: _round_to(p, s.dtype),
                                  params, stored)


# ---------------------------------------------------------------------------
# The network
# ---------------------------------------------------------------------------


def _on(flags, name: str):
    """Whether the variant ``name`` is on (a traced boolean)."""
    return flags[VARIANTS.index(name)] > 0


def _mm(flags):
    """Matrix product at float32 HIGHEST; the control first rounds both
    operands to float8_e4m3fn."""
    control = _on(flags, "control")

    def rnd(x):
        return jnp.where(control,
                         x.astype(jnp.float8_e4m3fn).astype(jnp.float32), x)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b),
                          precision=lax.Precision.HIGHEST)
    return mm


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _yarn_get_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(net: Net, positions, yarn: bool = True):
    """(cos, sin) of shape (S, rope/2) for the rotary channels."""
    dim, base = net.rope, net.theta
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    mscale = 1.0
    if yarn:
        factor, orig, fast, slow, ms, ms_all = net.yarn
        freq_inter = freq_extra / factor

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(corr(fast)), 0)
        high = min(math.ceil(corr(slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        keep = 1.0 - ramp
        inv = freq_inter * (1 - keep) + freq_extra * keep
        mscale = _yarn_get_mscale(factor, ms) / _yarn_get_mscale(factor, ms_all)
    else:
        inv = freq_extra
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale


def _rotate(x, cos, sin):
    """x (..., S, [H,] rope) by the halves convention."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def softmax_scale(net: Net, yarn: bool = True) -> float:
    scale = 1.0 / math.sqrt(net.nope + net.rope)
    factor, _, _, _, _, ms_all = net.yarn
    if yarn and ms_all:
        scale *= _yarn_get_mscale(factor, ms_all) ** 2
    return scale


def mla(p, net: Net, x, mm, plain):
    """x (B, S, d) -> (B, S, d), causal; ``plain`` (a traced boolean) drops
    YaRN for plain RoPE and the plain softmax scale."""
    B, S, _ = x.shape
    H = net.heads
    q = mm("bsd,de->bse", x, p["wq"]["w"]).reshape(B, S, H, net.nope + net.rope)
    q_n, q_r = q[..., :net.nope], q[..., net.nope:]
    ckv = mm("bsd,de->bse", x, p["wdkv"]["w"])
    c = _rms(ckv[..., :net.kv_rank], p["kv_norm"]["scale"], net.eps)
    k_r = ckv[..., net.kv_rank:]
    kv = mm("bsr,re->bse", c, p["wukv"]["w"]).reshape(B, S, H, net.nope + net.v)
    k_n, v = kv[..., :net.nope], kv[..., net.nope:]
    cos_y, sin_y = rope_tables(net, jnp.arange(S), True)
    cos_p, sin_p = rope_tables(net, jnp.arange(S), False)
    cos = jnp.where(plain, cos_p, cos_y)
    sin = jnp.where(plain, sin_p, sin_y)
    q_r = _rotate(q_r, cos[:, None, :], sin[:, None, :])
    k_r = _rotate(k_r, cos, sin)  # (B, S, rope), one per token
    s = (mm("bqhn,bkhn->bhqk", q_n, k_n) + mm("bqhr,bkr->bhqk", q_r, k_r))
    s = s * jnp.where(plain, softmax_scale(net, False), softmax_scale(net, True))
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    out = mm("bhqk,bkhv->bqhv", jax.nn.softmax(s, axis=-1), v)
    return mm("bse,ed->bsd", out.reshape(B, S, H * net.v), p["wo"]["w"])


def _swiglu_apply(p, x, mm):
    h = jax.nn.silu(mm("td,df->tf", x, p["wi"]["w"])) * mm("td,df->tf", x, p["wg"]["w"])
    return mm("tf,fd->td", h, p["wo"]["w"])


def _blocks(fn, x):
    """fn over token blocks of x (T, d)."""
    T = x.shape[0]
    if T <= FFN_BLOCK or T % FFN_BLOCK:
        return fn(x)
    xb = x.reshape(T // FFN_BLOCK, FFN_BLOCK, x.shape[-1])
    return lax.map(fn, xb).reshape(T, -1)


def route(p, net: Net, x, flags, seq: int):
    """Gates (T, held) of the held experts for tokens x (T, d), 0 where a
    token does not route to the expert."""
    logits = jnp.einsum("td,de->te", x, p["router"]["w"],
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, net.k)
    normed = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    if net.norm_topk:
        top_w = normed
    else:
        top_w = jnp.where(_on(flags, "renormalized_topk"), normed, top_w)
    top_w = top_w * net.route_scale
    experts = net.held_from + jnp.arange(net.held)
    hit = top_e[:, :, None] == experts[None, None, :]  # (T, k, held)
    # the capacity fault: per sequence, each expert keeps its first `cap`
    # assignments in (token, slot) order
    T = x.shape[0]
    cap = max(int(math.ceil(seq * net.k * CAPACITY_FACTOR
                            / net.experts_total)), 1)
    h = hit.reshape(T // seq, seq * net.k, net.held)
    rank = jnp.cumsum(h, axis=1) - 1
    kept = (h & (rank < cap)).reshape(T, net.k, net.held)
    hit = jnp.where(_on(flags, "capacity_routing"), kept, hit)
    return jnp.sum(jnp.where(hit, top_w[:, :, None], 0.0), axis=1)


def moe(p, net: Net, x, mm, flags):
    """x (B, S, d) -> the held experts' part plus the shared experts."""
    B, S, d = x.shape
    t = x.reshape(B * S, d)
    gates = route(p, net, t, flags, S)

    def experts(args):
        xb, gb = args

        def one(out, expert):
            wi, wg, wo, gate = expert
            h = (jax.nn.silu(mm("td,df->tf", xb, wi))
                 * mm("td,df->tf", xb, wg))
            return out + gate[:, None] * mm("tf,fd->td", h, wo), None

        out, _ = lax.scan(one, jnp.zeros_like(xb),
                          (p["wi"], p["wg"], p["wo"], gb.T))
        return out

    T = t.shape[0]
    if T > FFN_BLOCK and T % FFN_BLOCK == 0:
        nb = T // FFN_BLOCK
        y = lax.map(experts, (t.reshape(nb, FFN_BLOCK, d),
                              gates.reshape(nb, FFN_BLOCK, -1))).reshape(T, d)
    else:
        y = experts((t, gates))
    shared = _blocks(lambda xb: _swiglu_apply(p["shared"], xb, mm), t)
    y = y + jnp.where(_on(flags, "no_shared_expert"), 0.0, shared)
    return y.reshape(B, S, d)


def _block_apply(p, net: Net, x, mm, flags, dense: bool):
    x = x + mla(p["attn"], net, _rms(x, p["ln1"]["scale"], net.eps), mm,
                _on(flags, "plain_rope"))
    h = _rms(x, p["ln2"]["scale"], net.eps)
    if dense:
        B, S, d = h.shape
        y = _blocks(lambda xb: _swiglu_apply(p["mlp"], xb, mm),
                    h.reshape(B * S, d)).reshape(B, S, d)
    else:
        y = moe(p["moe"], net, h, mm, flags)
    return x + y


def forward(params, net: Net, tokens, flags, remat: bool = False):
    """tokens (B, S) -> logits (B, vocab) and value (B,) at the last
    position, under the variant ``flags`` (``variant_flags``). ``remat``
    recomputes each block in the backward pass."""
    mm = _mm(flags)
    tr = params["trunk"]
    x = tr["embed"][tokens]
    for dense, name in ((True, "first"), (False, "layers")):
        if name not in tr:
            continue

        def body(x, lp, dense=dense):
            return _block_apply(lp, net, x, mm, flags, dense), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = lax.scan(body, x, tr[name])
    h = _rms(x[:, -1], tr["final_norm"]["scale"], net.eps)
    heads = params["heads"]
    logits = mm("bd,da->ba", h, heads["policy"]["w"])
    value = mm("bd,do->bo", h, heads["value"]["w"])[:, 0] + heads["value"]["b"][0]
    return logits, value


# ---------------------------------------------------------------------------
# PAAC
# ---------------------------------------------------------------------------


def n_step_returns(rewards, dones, bootstrap, gamma):
    """(T, E) rewards/dones, (E,) bootstrap -> (T, E) returns."""
    ret, out = bootstrap, []
    for t in range(rewards.shape[0] - 1, -1, -1):
        ret = rewards[t] + gamma * (1.0 - dones[t].astype(ret.dtype)) * ret
        out.append(ret)
    return jnp.stack(out[::-1])


def _chunk_loss(params, net: Net, hp: Hyper, obs, actions, returns, keep,
                n, flags):
    """Equations (10) and (11) summed over one chunk of the batch, over
    the batch's ``n`` samples learnt from: the chunks' terms add up to the
    means. ``keep`` (0/1 a sample) leaves samples out."""
    logits, values = forward(params, net, obs, flags, remat=True)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[:, None], axis=1)[:, 0]
    adv = lax.stop_gradient(returns - values)
    policy = -jnp.sum(keep * adv * logp_a)
    entropy = -jnp.sum(keep[:, None] * jnp.exp(logp) * logp)
    value = jnp.sum(keep * jnp.square(returns - values))
    return (policy - hp.beta * entropy + hp.value_coef * value) / n


def loss_and_grads(params, net: Net, hp: Hyper, obs, actions, returns,
                   flags):
    """The loss over the (T, E) batch and its gradient, the batch taken
    ``LEARN_CHUNK`` contexts at a time. The half-batch fault learns from
    the first half of the games alone."""
    T, E = actions.shape
    n = T * E
    size = next(c for c in range(min(LEARN_CHUNK, n), 0, -1) if n % c == 0)
    half = _on(flags, "half_batch")
    keep = jnp.where(half & (jnp.arange(E) >= E // 2), 0.0, 1.0)
    keep = jnp.broadcast_to(keep, (T, E))
    n_kept = jnp.where(half, T * (E // 2), n).astype(jnp.float32)
    chunks = (obs.reshape(n // size, size, -1),
              actions.reshape(n // size, size),
              returns.reshape(n // size, size),
              keep.reshape(n // size, size))
    grad = jax.value_and_grad(_chunk_loss)

    def body(acc, xs):
        loss, g = grad(params, net, hp, *xs, n_kept, flags)
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(body, zero, chunks)
    return loss, grads


@partial(jax.jit, static_argnames=("net", "hp", "game", "t_max"),
         donate_argnums=(0, 1))
def _step(params, sq, state, key, flags, net, hp, game, t_max):
    def act(carry, _):
        state, key = carry
        key, k_act, k_env = jax.random.split(key, 3)
        obs = game.observe(state)
        logits, _ = forward(params, net, obs, flags)
        action = jax.random.categorical(k_act, logits)
        state, reward, done = game.step(state, action, k_env)
        return (state, key), (obs, action, reward, done)

    (state, key), (obs, actions, rewards, dones) = lax.scan(
        act, (state, key), None, length=t_max)
    _, boot = forward(params, net, game.observe(state), flags)
    returns = n_step_returns(rewards, dones, lax.stop_gradient(boot),
                             hp.gamma)
    loss, grads = loss_and_grads(params, net, hp, obs, actions, returns,
                                 flags)
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, hp.clip / jnp.maximum(norm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    sq = jax.tree_util.tree_map(
        lambda s, g: hp.decay * s + (1.0 - hp.decay) * jnp.square(g), sq, grads)
    params = store(jax.tree_util.tree_map(
        lambda p, g, s: p - hp.lr * g / (jnp.sqrt(s) + hp.eps), params, grads,
        sq), net)
    return params, sq, state, key, loss, grads


def variant_flags(control: bool = False, fault: Optional[str] = None):
    """The 0/1 flags of ``VARIANTS`` for the control and one fault."""
    return jnp.asarray([float(control)] + [float(f == fault) for f in FAULTS],
                       jnp.float32)


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def train(config: dict, seed: int, *, n_envs: int, lanes: int, t_max: int,
          lr: float, steps: int = 3, dtype=jnp.float32,
          fault: Optional[str] = None, lane_keys_per_step: bool = False):
    """Follow ``steps`` PAAC iterations from ``seed`` on one lane of
    ``n_envs`` games that carries its acting key from step to step.
    Returns a dict with ``losses`` (per step), ``grads`` (the first step's
    clipped gradient), ``params0`` and ``params`` (after ``steps``), the
    arrays on the host."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if lanes != 1 or lane_keys_per_step:
        raise ValueError("the token reference follows one lane")
    net = Net.from_config(config)
    hp = Hyper.from_config(config, lr)
    g = config["game"]
    game = Game(n_envs, net.vocab, g["ctx"], g["k"], g["horizon"])
    flags = variant_flags(jnp.dtype(dtype) != jnp.float32, fault)
    key, k_init, k_env = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_params(k_init, net)
    params0 = _host(params)
    sq = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = game.reset(k_env)
    losses: List[float] = []
    grads0 = None
    for _ in range(steps):
        params, sq, state, key, loss, grads = _step(
            params, sq, state, key, flags, net, hp, game, t_max)
        losses.append(float(loss))
        if grads0 is None:
            grads0 = _host(grads)
        del grads
    return {"losses": losses, "grads": grads0, "params0": params0,
            "params": _host(params)}
