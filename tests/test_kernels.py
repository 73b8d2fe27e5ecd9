"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle across
shape/dtype sweeps, plus hypothesis property checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# exemplar_gains
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,d", [(16, 16, 4), (37, 53, 19), (128, 64, 33),
                                   (8, 200, 3), (256, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exemplar_gains_shapes(n, m, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(n * m), 3)
    X = _rand(k1, (n, d), dtype)
    E = _rand(k2, (m, d), dtype)
    cm = jnp.abs(_rand(k3, (m,), jnp.float32)) * 4
    got = ops.exemplar_gains(X, E, cm, impl="pallas", bn=16, bm=16)
    want = ref.exemplar_gains(X.astype(jnp.float32), E.astype(jnp.float32), cm)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == jnp.bfloat16
                               else 1e-5, atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 40), m=st.integers(1, 40), d=st.integers(1, 24),
       seed=st.integers(0, 99))
def test_exemplar_gains_property(n, m, d, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = _rand(k1, (n, d))
    E = _rand(k2, (m, d))
    cm = jnp.abs(_rand(k3, (m,))) * 2
    got = ops.exemplar_gains(X, E, cm, impl="pallas", bn=8, bm=8)
    want = ref.exemplar_gains(X, E, cm)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert bool(jnp.all(got >= -1e-6))   # gains of monotone f are nonnegative


# ---------------------------------------------------------------------------
# rbf_kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,d,h", [(16, 16, 8, 0.5), (33, 65, 7, 1.0),
                                     (128, 32, 64, 0.25)])
def test_rbf_kernel(n, m, d, h):
    k1, k2 = jax.random.split(jax.random.PRNGKey(7), 2)
    X = _rand(k1, (n, d), scale=0.5)
    Y = _rand(k2, (m, d), scale=0.5)
    got = ops.rbf_kernel(X, Y, h, impl="pallas", bn=16, bm=16)
    want = ref.rbf_kernel(X, Y, h)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # kernel properties: K(x,x)=1 (±fp cancellation amplified by 1/h²),
    # 0 <= K <= 1
    Kxx = ops.rbf_kernel(X, X, h, impl="pallas", bn=16, bm=16)
    np.testing.assert_allclose(jnp.diag(Kxx), 1.0, atol=3e-3 / h / h)
    assert bool(jnp.all((got >= 0) & (got <= 1 + 1e-6)))  # underflow → 0 ok


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,Hkv,S,T,D", [
    (2, 4, 2, 16, 16, 8),     # GQA square
    (1, 8, 1, 32, 32, 16),    # MQA
    (2, 4, 4, 8, 24, 8),      # decode-ish: S < T (causal offset)
    (1, 2, 2, 64, 64, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, H, Hkv, S, T, D, causal):
    ks = jax.random.split(jax.random.PRNGKey(B * S + D), 3)
    q = _rand(ks[0], (B, H, S, D))
    k = _rand(ks[1], (B, Hkv, T, D))
    v = _rand(ks[2], (B, Hkv, T, D))
    got = ops.flash_attention(q, k, v, causal=causal, impl="pallas",
                              bq=8, bk=8)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (2, 4, 16, 8), jnp.bfloat16)
    k = _rand(ks[1], (2, 2, 16, 8), jnp.bfloat16)
    v = _rand(ks[2], (2, 2, 16, 8), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, impl="pallas", bq=8, bk=8)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=3e-2, atol=3e-2)


def test_attention_kv_valid_len_masks_unfilled_cache():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, T, D = 1, 2, 32, 8
    q = _rand(ks[0], (B, H, 1, D))
    k = _rand(ks[1], (B, H, T, D))
    v = _rand(ks[2], (B, H, T, D))
    # poisoning positions >= 10 must not change the output
    k_poison = k.at[:, :, 10:].set(999.0)
    v_poison = v.at[:, :, 10:].set(-999.0)
    a = ref.flash_attention(q, k, v, causal=False, kv_valid_len=10)
    b = ref.flash_attention(q, k_poison, v_poison, causal=False,
                            kv_valid_len=10)
    np.testing.assert_allclose(a, b, rtol=1e-6)


# ---------------------------------------------------------------------------
# wkv6 + chunked GLA
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,T,Dk,Dv", [(2, 3, 16, 8, 8), (1, 2, 64, 16, 16),
                                         (2, 1, 32, 4, 8)])
def test_wkv6_kernel(B, H, T, Dk, Dv):
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    r = _rand(ks[0], (B, H, T, Dk), scale=0.3)
    k = _rand(ks[1], (B, H, T, Dk), scale=0.3)
    v = _rand(ks[2], (B, H, T, Dv), scale=0.3)
    w = jax.nn.sigmoid(_rand(ks[3], (B, H, T, Dk)) + 2.0)
    u = _rand(ks[4], (H, Dk), scale=0.1)
    got = ops.wkv6(r, k, v, w, u, impl="pallas", bt=8)
    want = ref.wkv6(r, k, v, w, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_gla_chunked_matches_wkv6_and_step():
    from repro.models.layers import gla_chunked, gla_step
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, H, T, Dk, Dv = 2, 2, 96, 8, 8
    r = _rand(ks[0], (B, H, T, Dk), scale=0.4)
    k = _rand(ks[1], (B, H, T, Dk), scale=0.4)
    v = _rand(ks[2], (B, H, T, Dv), scale=0.4)
    w = jax.nn.sigmoid(_rand(ks[3], (B, H, T, Dk)) + 2.0)
    u = _rand(ks[4], (H, Dk), scale=0.1)
    want = ref.wkv6(r, k, v, w, u)
    got, S = gla_chunked(r, k, v, jnp.log(w), u, chunk=32)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # recurrent replay reaches the same final state
    st_ = jnp.zeros((B, H, Dk, Dv))
    for t in range(T):
        _, st_ = gla_step(r[:, :, t], k[:, :, t], v[:, :, t], w[:, :, t],
                          u, st_)
    np.testing.assert_allclose(S, st_, rtol=2e-3, atol=2e-3)
