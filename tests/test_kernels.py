"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.attention import flash_attention, gqa_attention, mha_ref
from repro.kernels.similarity import similarity_pallas, similarity_ref

KEY = jax.random.PRNGKey(0)


# ------------------------------ similarity --------------------------------

@pytest.mark.parametrize("m,b,n", [(64, 32, 16), (256, 256, 256), (130, 70, 33),
                                   (8, 8, 4), (512, 128, 1024),
                                   (300, 100, 20)])   # pads both m and b
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["inverse_distance", "gaussian"])
def test_similarity_kernel_matches_ref(m, b, n, dtype, kind):
    x = jax.random.normal(KEY, (m, n), dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (b, n), dtype)
    ref = similarity_ref(x, y, 1.7, kind)
    out = similarity_pallas(x, y, 1.7, kind, interpret=True)
    tol = 5e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(4, 80), b=st.integers(4, 80), n=st.integers(2, 64),
       gamma=st.floats(0.5, 4.0))
def test_similarity_kernel_hypothesis(m, b, n, gamma):
    x = jax.random.normal(jax.random.PRNGKey(m * 7 + n), (m, n))
    y = jax.random.normal(jax.random.PRNGKey(b * 13 + n), (b, n))
    ref = similarity_ref(x, y, gamma)
    out = similarity_pallas(x, y, gamma, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_similarity_properties():
    x = jax.random.normal(KEY, (32, 8))
    s = similarity_ref(x, x, 1.0)
    # self-similarity: ~1 up to fp32 cancellation in the ||x||^2+||y||^2-2xy trick
    assert np.allclose(np.asarray(jnp.diag(s)), 1.0, atol=5e-3)
    assert np.allclose(np.asarray(s), np.asarray(s.T), atol=1e-5)  # symmetry
    assert float(s.min()) > 0 and float(s.max()) <= 1.0 + 1e-6     # range


# ------------------------------ attention ---------------------------------

@pytest.mark.parametrize("B,S,H,hd", [(2, 128, 2, 64), (1, 256, 4, 32),
                                      (2, 200, 2, 64), (1, 64, 1, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, S, H, hd, causal):
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    ref = mha_ref(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, bq=64, bkv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = jax.random.normal(KEY, (1, 128, 2, 32), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 32), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 32), dtype)
    ref = mha_ref(q, k, v)
    out = flash_attention(q, k, v, bq=64, bkv=64, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_gqa_wrapper_expands_kv():
    q = jax.random.normal(KEY, (2, 64, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 32))
    out = gqa_attention(q, k, v, impl="interpret")
    kx = jnp.repeat(k, 4, 2)
    vx = jnp.repeat(v, 4, 2)
    ref = mha_ref(q, kx, vx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(S=st.integers(16, 160), hd=st.sampled_from([16, 32, 64]))
def test_flash_attention_hypothesis(S, hd):
    q = jax.random.normal(jax.random.PRNGKey(S), (1, S, 2, hd))
    k = jax.random.normal(jax.random.PRNGKey(S + 1), (1, S, 2, hd))
    v = jax.random.normal(jax.random.PRNGKey(S + 2), (1, S, 2, hd))
    ref = mha_ref(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, bq=32, bkv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)
