"""Per-kernel correctness: sweep shapes/dtypes, assert_allclose vs ref.py.

Kernels run in interpret mode on CPU (the container target); the oracles are
pure jnp.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.nstep_returns import nstep_returns_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


# ---------------------------------------------------------------- nstep
@pytest.mark.parametrize("E,T", [(1, 1), (7, 5), (32, 64), (33, 17), (300, 5)])
@pytest.mark.parametrize("gamma", [0.9, 0.99])
def test_nstep_returns(E, T, gamma, key):
    r = jax.random.normal(key, (E, T))
    d = jax.random.bernoulli(key, 0.3, (E, T))
    b = jax.random.normal(key, (E,))
    out = nstep_returns_pallas(r, d, b, gamma, block_e=8, interpret=True)
    ref = R.nstep_returns_ref(r, d, b, gamma)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_nstep_matches_paper_hand_example():
    # hand-computed: r=[1,0,2], gamma=0.5, bootstrap=4, no terminals
    # R3 = 2 + .5*4 = 4 ; R2 = 0 + .5*4 = 2 ; R1 = 1 + .5*2 = 2
    r = jnp.array([[1.0, 0.0, 2.0]])
    d = jnp.zeros((1, 3), bool)
    b = jnp.array([4.0])
    out = nstep_returns_pallas(r, d, b, 0.5, interpret=True)
    np.testing.assert_allclose(out[0], [2.0, 2.0, 4.0])
    # terminal at t=1 cuts the bootstrap: R2 = 0 (done), R1 = 1 + .5*0
    d = jnp.array([[False, True, False]])
    out = nstep_returns_pallas(r, d, b, 0.5, interpret=True)
    np.testing.assert_allclose(out[0], [1.0, 0.0, 4.0])


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("Sq,Sk,H,Hkv,D", [
    (64, 64, 4, 4, 32),
    (128, 128, 4, 2, 64),
    (100, 100, 8, 1, 64),   # padded seq, MQA
    (256, 256, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 37])
def test_flash_attention(Sq, Sk, H, Hkv, D, dtype, window, key):
    B = 2
    q = jax.random.normal(key, (B, Sq, H, D), dtype)
    k = jax.random.normal(key, (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(key, (B, Sk, Hkv, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


def test_flash_non_causal(key):
    B, S, H, D = 2, 96, 4, 32
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(key, (B, S, H, D))
    v = jax.random.normal(key, (B, S, H, D))
    out = flash_attention_pallas(q, k, v, causal=False, block_q=32, block_k=32,
                                 interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- decode
@pytest.mark.parametrize("S,H,Hkv,D,pos", [
    (128, 4, 4, 32, 80),
    (300, 8, 2, 64, 299),
    (512, 8, 1, 128, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(S, H, Hkv, D, pos, dtype, key):
    B = 2
    q = jax.random.normal(key, (B, H, D), dtype)
    kc = jax.random.normal(key, (B, S, Hkv, D), dtype)
    vc = jax.random.normal(key, (B, S, Hkv, D), dtype)
    out = decode_attention_pallas(q, kc, vc, pos, block_k=128, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol
    )


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("S,H,P,N,chunk", [
    (64, 2, 16, 8, 16),
    (256, 4, 32, 16, 64),
    (128, 8, 64, 64, 128),  # single chunk
])
def test_ssd_scan(S, H, P, N, chunk, key):
    B = 2
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H)))
    A_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    Bm = jax.random.normal(key, (B, S, N))
    Cm = jax.random.normal(key, (B, S, N))
    Dv = jnp.ones((H,))
    y = ssd_scan_pallas(x, dt, A_log, Bm, Cm, Dv, chunk=chunk, interpret=True)
    ref, _ = R.ssd_scan_ref(x, dt, A_log, Bm, Cm, Dv)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=2e-3)


def test_ssd_scan_matches_model_chunked(key):
    """The kernel, the chunked model path and the sequential oracle agree."""
    from repro.models.ssm import ssd_chunked

    B, S, H, P, N = 2, 128, 4, 32, 16
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H)))
    A_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    Bm = jax.random.normal(key, (B, S, N))
    Cm = jax.random.normal(key, (B, S, N))
    Dv = jnp.ones((H,))
    y_k = ssd_scan_pallas(x, dt, A_log, Bm, Cm, Dv, chunk=32,
                          interpret=True)
    y_m, state_m = ssd_chunked(x, dt, A_log, Bm, Cm, Dv, chunk=32)
    y_r, state_r = R.ssd_scan_ref(x, dt, A_log, Bm, Cm, Dv)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_m, y_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state_m, state_r, rtol=1e-4, atol=1e-4)
