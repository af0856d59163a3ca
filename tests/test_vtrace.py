"""V-trace invariants, deterministically (no dev extras required).

The hypothesis property suite in ``tests/test_returns.py`` fuzzes the same
invariants over random shapes/inputs; this module pins them on seeded
inputs so tier-1 (no ``hypothesis`` installed) still covers the V-trace
math, plus the Pallas-kernel/reference parity sweep.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.returns import n_step_returns, vtrace_returns
from repro.kernels import ref as R
from repro.kernels.vtrace import vtrace_returns_pallas


def _inputs(seed, E=4, T=9, rho_scale=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        rewards=jax.random.normal(ks[0], (E, T)),
        dones=jax.random.bernoulli(ks[1], 0.25, (E, T)),
        values=jax.random.normal(ks[2], (E, T)),
        bootstrap=jax.random.normal(ks[3], (E,)),
        rho=jnp.exp(rho_scale * jax.random.normal(ks[4], (E, T))),
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_on_policy_equals_nstep(seed):
    """rho == 1 with ρ̄, c̄ >= 1: the recursion telescopes into n-step."""
    x = _inputs(seed)
    vs, pg_adv = vtrace_returns(
        x["rewards"], x["dones"], x["values"], x["bootstrap"],
        jnp.ones_like(x["rho"]), 0.97, rho_bar=1.0, c_bar=1.0,
    )
    ns = n_step_returns(x["rewards"], x["dones"], x["bootstrap"], 0.97)
    np.testing.assert_allclose(vs, ns, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(pg_adv, ns - x["values"], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seed", [0, 3])
def test_unclipped_equals_importance_weighted_nstep(seed):
    """ρ̄ = c̄ → ∞: v_s - V_s = Σ_t γ^{t-s} (Π nd·w) δ_t with raw ratios."""
    x = _inputs(seed)
    E, T = x["rewards"].shape
    vs, _ = vtrace_returns(
        x["rewards"], x["dones"], x["values"], x["bootstrap"], x["rho"],
        0.9, rho_bar=1e12, c_bar=1e12,
    )
    r = np.asarray(x["rewards"], np.float32)
    nd = 1.0 - np.asarray(x["dones"], np.float32)
    v = np.asarray(x["values"], np.float32)
    b = np.asarray(x["bootstrap"], np.float32)
    w = np.asarray(x["rho"], np.float32)
    v_next = np.concatenate([v[:, 1:], b[:, None]], axis=1)
    delta = w * (r + 0.9 * nd * v_next - v)
    expect = v.copy()
    for s in range(T):
        for t in range(s, T):
            disc = np.prod(nd[:, s:t] * w[:, s:t], axis=1) * 0.9 ** (t - s)
            expect[:, s] += disc * delta[:, t]
    np.testing.assert_allclose(vs, expect, rtol=1e-3, atol=1e-3)


def test_monotone_nonexpansive_in_c_bar():
    """Raising c̄ moves the targets monotonically (for nonnegative TD
    errors) and stops moving them at all once c̄ saturates the ratios."""
    x = _inputs(0)
    # values = 0, rewards >= 0 => every delta >= 0 => targets monotone in c̄
    rewards = jnp.abs(x["rewards"])
    zeros = jnp.zeros_like(x["values"])
    prev = None
    for c_bar in (0.0, 0.25, 0.5, 1.0, 2.0, 8.0):
        vs, _ = vtrace_returns(rewards, x["dones"], zeros,
                               jnp.zeros_like(x["bootstrap"]), x["rho"],
                               0.95, rho_bar=1e9, c_bar=c_bar)
        if prev is not None:
            assert (np.asarray(vs) >= np.asarray(prev) - 1e-5).all()
        prev = vs
    # saturation: c̄ at/above the max ratio is a fixed point of raising c̄
    cap = float(jnp.max(x["rho"]))
    vs_a, adv_a = vtrace_returns(x["rewards"], x["dones"], x["values"],
                                 x["bootstrap"], x["rho"], 0.95,
                                 rho_bar=1e9, c_bar=cap)
    vs_b, adv_b = vtrace_returns(x["rewards"], x["dones"], x["values"],
                                 x["bootstrap"], x["rho"], 0.95,
                                 rho_bar=1e9, c_bar=10.0 * cap)
    np.testing.assert_allclose(vs_a, vs_b, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(adv_a, adv_b, rtol=1e-6, atol=1e-6)


def test_c_bar_zero_is_one_step_td():
    """c̄ = 0 cuts all bootstrapping through future corrections: the target
    collapses to V + ρ̄-clipped one-step TD error."""
    x = _inputs(1)
    vs, _ = vtrace_returns(x["rewards"], x["dones"], x["values"],
                           x["bootstrap"], x["rho"], 0.9,
                           rho_bar=1.0, c_bar=0.0)
    v = np.asarray(x["values"], np.float32)
    nd = 1.0 - np.asarray(x["dones"], np.float32)
    b = np.asarray(x["bootstrap"], np.float32)
    v_next = np.concatenate([v[:, 1:], b[:, None]], axis=1)
    rc = np.minimum(np.asarray(x["rho"], np.float32), 1.0)
    td = v + rc * (np.asarray(x["rewards"], np.float32)
                   + 0.9 * nd * v_next - v)
    np.testing.assert_allclose(vs, td, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- kernel
@pytest.mark.parametrize("E,T", [(1, 1), (5, 9), (32, 33), (17, 8),
                                 (300, 5)])
@pytest.mark.parametrize("rho_bar,c_bar", [(1.0, 1.0), (2.0, 1.0),
                                           (1e9, 1e9)])
def test_vtrace_kernel_matches_scan_and_ref(E, T, rho_bar, c_bar):
    x = _inputs(7, E=E, T=T)
    args = (x["rewards"], x["dones"], x["values"], x["bootstrap"], x["rho"],
            0.97, rho_bar, c_bar)
    vs_scan, adv_scan = vtrace_returns(*args)
    vs_ref, adv_ref = R.vtrace_returns_ref(*args)
    vs_k, adv_k = vtrace_returns_pallas(*args, block_e=8, interpret=True)
    np.testing.assert_allclose(vs_scan, vs_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(adv_scan, adv_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(vs_k, vs_scan, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(adv_k, adv_scan, rtol=1e-5, atol=1e-5)
