"""Properties of the return estimators (hypothesis) — system invariants.

``hypothesis`` is a dev-extra (see requirements-dev.txt) — skip the module
cleanly when it isn't installed instead of erroring the whole collection.
"""
import os

import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings  # noqa: E402
from hypothesis.extra import numpy as hnp  # noqa: E402

from repro.core.returns import (  # noqa: E402
    gae_advantages,
    n_step_returns,
    vtrace_returns,
)
from repro.kernels.vtrace import vtrace_returns_pallas  # noqa: E402

hypothesis.settings.register_profile("ci", deadline=None, max_examples=25)
hypothesis.settings.register_profile("dev", deadline=None, max_examples=100)
hypothesis.settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))


@given(
    rewards=hnp.arrays(np.float32, (4, 7), elements=st.floats(-5, 5, width=32)),
    dones=hnp.arrays(np.bool_, (4, 7)),
    bootstrap=hnp.arrays(np.float32, (4,), elements=st.floats(-5, 5, width=32)),
    gamma=st.floats(0.5, 0.999),
)
def test_nstep_recursion_invariant(rewards, dones, bootstrap, gamma):
    """R_t = r_t + gamma*(1-done_t)*R_{t+1} holds pointwise."""
    R = np.asarray(n_step_returns(jnp.asarray(rewards), jnp.asarray(dones),
                                  jnp.asarray(bootstrap), gamma))
    nxt = np.concatenate([R[:, 1:], bootstrap[:, None]], axis=1)
    expect = rewards + gamma * (1.0 - dones.astype(np.float32)) * nxt
    np.testing.assert_allclose(R, expect, rtol=1e-5, atol=1e-5)


@given(
    rewards=hnp.arrays(np.float32, (3, 9), elements=st.floats(0, 1, width=32)),
    gamma=st.floats(0.5, 0.99),
)
def test_nstep_bounds_nonneg_rewards(rewards, gamma):
    """With r in [0,1], no terminals, zero bootstrap: 0 <= R_t <= 1/(1-gamma)."""
    R = np.asarray(
        n_step_returns(jnp.asarray(rewards), jnp.zeros((3, 9), bool),
                       jnp.zeros((3,)), gamma)
    )
    assert (R >= -1e-5).all()
    assert (R <= 1.0 / (1.0 - gamma) + 1e-4).all()


@given(
    dones_col=st.integers(0, 6),
)
def test_terminal_cuts_credit(dones_col):
    """Rewards after a terminal never flow into returns before it."""
    E, T = 1, 7
    rewards = np.zeros((E, T), np.float32)
    rewards[0, -1] = 100.0
    dones = np.zeros((E, T), bool)
    dones[0, dones_col] = True
    R = np.asarray(n_step_returns(jnp.asarray(rewards), jnp.asarray(dones),
                                  jnp.zeros((E,)), 0.9))
    if dones_col < T - 1:
        assert abs(R[0, 0]) < 1e-5  # reward at T-1 blocked by terminal
    else:
        assert R[0, 0] > 0


# ---------------------------------------------------------------------------
# V-trace properties (the pipelined learner's targets)
# ---------------------------------------------------------------------------

_rewards = hnp.arrays(np.float32, (4, 7), elements=st.floats(-5, 5, width=32))
_dones = hnp.arrays(np.bool_, (4, 7))
_values = hnp.arrays(np.float32, (4, 7), elements=st.floats(-5, 5, width=32))
_boot = hnp.arrays(np.float32, (4,), elements=st.floats(-5, 5, width=32))
_logw = hnp.arrays(np.float32, (4, 7), elements=st.floats(-2, 2, width=32))


@given(rewards=_rewards, dones=_dones, values=_values, bootstrap=_boot,
       gamma=st.floats(0.5, 0.999))
def test_vtrace_on_policy_equals_nstep(rewards, dones, values, bootstrap,
                                       gamma):
    """On-policy behaviour (rho == 1) with ρ̄, c̄ >= 1: V-trace targets
    equal the paper's n-step returns pointwise and the pg advantage is the
    paper's (R_t - V_t)."""
    vs, pg_adv = vtrace_returns(
        jnp.asarray(rewards), jnp.asarray(dones), jnp.asarray(values),
        jnp.asarray(bootstrap), jnp.ones((4, 7), jnp.float32), gamma,
        rho_bar=1.0, c_bar=1.0,
    )
    ns = np.asarray(n_step_returns(jnp.asarray(rewards), jnp.asarray(dones),
                                   jnp.asarray(bootstrap), gamma))
    np.testing.assert_allclose(vs, ns, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pg_adv, ns - values, rtol=1e-4, atol=1e-4)


@given(rewards=_rewards, dones=_dones, values=_values, bootstrap=_boot,
       log_rho=hnp.arrays(np.float32, (4, 7),
                          elements=st.floats(-1, 1, width=32)),
       gamma=st.floats(0.5, 0.99))
def test_vtrace_unclipped_is_importance_weighted_nstep(rewards, dones, values,
                                                       bootstrap, log_rho,
                                                       gamma):
    """ρ̄ = c̄ → ∞: v_s = V_s + Σ_t γ^{t-s}(Π_{i<t} nd_i·w_i)·w_t·δ_t —
    the fully importance-weighted n-step correction, by the definition."""
    rho = np.exp(log_rho).astype(np.float32)
    vs, _ = vtrace_returns(
        jnp.asarray(rewards), jnp.asarray(dones), jnp.asarray(values),
        jnp.asarray(bootstrap), jnp.asarray(rho), gamma,
        rho_bar=1e12, c_bar=1e12,
    )
    # float64 ground truth straight from the definition (double loop)
    nd = (1.0 - dones.astype(np.float64))
    w = rho.astype(np.float64)
    v = values.astype(np.float64)
    v_next = np.concatenate([v[:, 1:], bootstrap[:, None].astype(np.float64)],
                            axis=1)
    delta = w * (rewards.astype(np.float64) + gamma * nd * v_next - v)
    expect = v.copy()
    T = rewards.shape[1]
    for s in range(T):
        for t in range(s, T):
            disc = np.prod(nd[:, s:t] * w[:, s:t], axis=1) * gamma ** (t - s)
            expect[:, s] += disc * delta[:, t]
    np.testing.assert_allclose(vs, expect, rtol=1e-2, atol=1e-2)


@given(rewards=hnp.arrays(np.float32, (3, 8),
                          elements=st.floats(0, 5, width=32)),
       dones=hnp.arrays(np.bool_, (3, 8)),
       log_rho=hnp.arrays(np.float32, (3, 8),
                          elements=st.floats(-1, 1, width=32)),
       c_bars=st.tuples(st.floats(0.0, 4.0), st.floats(0.0, 4.0)),
       gamma=st.floats(0.5, 0.99))
def test_vtrace_monotone_nonexpansive_in_c_bar(rewards, dones, log_rho,
                                               c_bars, gamma):
    """Targets are monotone non-expansive in c̄: with nonnegative TD errors
    raising c̄ never lowers a target, and raising c̄ past the largest ratio
    changes nothing (the clip has saturated)."""
    rho = jnp.exp(jnp.asarray(log_rho))
    zeros = jnp.zeros((3, 8), jnp.float32)
    zb = jnp.zeros((3,), jnp.float32)
    lo, hi = min(c_bars), max(c_bars)
    vs_lo, _ = vtrace_returns(jnp.asarray(rewards), jnp.asarray(dones), zeros,
                              zb, rho, gamma, rho_bar=1e9, c_bar=lo)
    vs_lo = np.asarray(vs_lo)
    vs_hi, _ = vtrace_returns(jnp.asarray(rewards), jnp.asarray(dones), zeros,
                              zb, rho, gamma, rho_bar=1e9, c_bar=hi)
    tol = 1e-4 + 1e-5 * np.abs(vs_lo)  # scale-relative fp32 slack
    assert (np.asarray(vs_hi) >= vs_lo - tol).all()
    cap = float(jnp.max(rho))
    vs_a, _ = vtrace_returns(jnp.asarray(rewards), jnp.asarray(dones), zeros,
                             zb, rho, gamma, rho_bar=1e9, c_bar=cap)
    vs_b, _ = vtrace_returns(jnp.asarray(rewards), jnp.asarray(dones), zeros,
                             zb, rho, gamma, rho_bar=1e9, c_bar=2.0 * cap)
    np.testing.assert_allclose(vs_a, vs_b, rtol=1e-6, atol=1e-6)


@given(rewards=_rewards, dones=_dones, values=_values, bootstrap=_boot,
       log_rho=_logw, gamma=st.floats(0.5, 0.999),
       rho_bar=st.floats(0.5, 4.0), c_bar=st.floats(0.1, 2.0))
def test_vtrace_pallas_matches_reference_scan(rewards, dones, values,
                                              bootstrap, log_rho, gamma,
                                              rho_bar, c_bar):
    """The fused Pallas kernel matches the lax.scan reference to 1e-5."""
    rho = jnp.exp(jnp.asarray(log_rho))
    args = (jnp.asarray(rewards), jnp.asarray(dones), jnp.asarray(values),
            jnp.asarray(bootstrap), rho, gamma, rho_bar, c_bar)
    vs_ref, adv_ref = vtrace_returns(*args)
    vs_k, adv_k = vtrace_returns_pallas(*args, block_e=2, interpret=True)
    np.testing.assert_allclose(vs_k, vs_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(adv_k, adv_ref, rtol=1e-5, atol=1e-5)


def test_gae_lambda1_equals_nstep():
    """GAE(lambda=1) returns == n-step discounted returns."""
    key = jax.random.PRNGKey(0)
    E, T = 4, 11
    rewards = jax.random.normal(key, (E, T))
    dones = jax.random.bernoulli(key, 0.2, (E, T))
    values = jax.random.normal(key, (E, T))
    boot = jax.random.normal(key, (E,))
    adv, rets = gae_advantages(rewards, dones, values, boot, 0.95, lam=1.0)
    nstep = n_step_returns(rewards, dones, boot, 0.95)
    np.testing.assert_allclose(rets, nstep, rtol=1e-4, atol=1e-4)


def test_gae_lambda0_is_td():
    key = jax.random.PRNGKey(1)
    E, T = 2, 6
    rewards = jax.random.normal(key, (E, T))
    dones = jnp.zeros((E, T), bool)
    values = jax.random.normal(key, (E, T))
    boot = jax.random.normal(key, (E,))
    adv, _ = gae_advantages(rewards, dones, values, boot, 0.9, lam=0.0)
    nxt = jnp.concatenate([values[:, 1:], boot[:, None]], axis=1)
    td = rewards + 0.9 * nxt - values
    np.testing.assert_allclose(adv, td, rtol=1e-5, atol=1e-5)
