"""Per-kernel allclose sweeps vs ref.py oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # graceful degrade: example sweeps
    from _hyp_fallback import given, settings, strategies as st

from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gbm_predict import gbm_predict
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.wkv6 import wkv6


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


ATTN_CASES = [
    # (B, S, H, KV, hd, causal, window, cap, dtype, tol)
    (2, 256, 4, 2, 64, True, 0, 0.0, jnp.float32, 2e-5),
    (1, 384, 4, 1, 128, True, 64, 0.0, jnp.float32, 2e-5),
    (2, 128, 8, 8, 64, True, 0, 50.0, jnp.float32, 2e-5),
    (1, 256, 4, 4, 64, False, 0, 0.0, jnp.float32, 2e-5),
    (1, 256, 4, 2, 64, True, 128, 30.0, jnp.bfloat16, 3e-2),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_ref(case):
    B, S, H, KV, hd, causal, window, cap, dtype, tol = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          q_block=128, kv_block=128, interpret=True)
    ref = R.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=causal,
                          window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol * 10)


@settings(max_examples=12, deadline=None)
@given(B=st.integers(1, 2), nq=st.integers(1, 3), H=st.sampled_from([2, 4]),
       G=st.sampled_from([1, 2]), hd=st.sampled_from([32, 64]),
       causal=st.booleans())
def test_flash_attention_hypothesis(B, nq, H, G, hd, causal):
    S = nq * 64
    KV = max(H // G, 1)
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + S + H + hd), 3)
    q = _rand(ks[0], (B, S, H, hd))
    k = _rand(ks[1], (B, S, KV, hd))
    v = _rand(ks[2], (B, S, KV, hd))
    out = flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64,
                          interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-4)


@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 2), L=st.sampled_from([128, 256, 384]),
       KV=st.sampled_from([1, 2, 4]), G=st.sampled_from([1, 2, 4]),
       window=st.sampled_from([0, 64]))
def test_decode_attention_hypothesis(B, L, KV, G, window):
    H, hd = KV * G, 64
    pos = L // 2 + 7
    ks = jax.random.split(jax.random.PRNGKey(L + KV * 10 + G), 3)
    q = _rand(ks[0], (B, H, hd))
    kc = _rand(ks[1], (B, L, KV, hd))
    vc = _rand(ks[2], (B, L, KV, hd))
    out = decode_attention(q, kc, vc, jnp.int32(pos), window=window,
                           block=64, interpret=True)
    ref = R.decode_attention_ref(q, kc, vc, pos=pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-4)


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 2), n_chunks=st.integers(2, 6),
       H=st.sampled_from([2, 4]), hd=st.sampled_from([16, 32]),
       decay=st.floats(0.2, 2.0))
def test_wkv6_hypothesis(B, n_chunks, H, hd, decay):
    S = 16 * n_chunks
    ks = jax.random.split(jax.random.PRNGKey(B + S + H + hd), 5)
    r, k, v = [_rand(ks[i], (B, S, H, hd), scale=0.5) for i in range(3)]
    # RWKV6 decay domain: w = exp(-exp(x)) with trained x <= ~2
    # (the kernel clamps log w at -9, outside this domain)
    x_w = jnp.clip(_rand(ks[3], (B, S, H, hd), scale=decay), -8.0, 2.0)
    w = jnp.exp(-jnp.exp(x_w))
    u = _rand(ks[4], (H, hd), scale=0.3)
    y_k, s_k = wkv6(r, k, v, w, u, interpret=True)
    y_r, s_r = R.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=2e-4,
                               rtol=1e-3)


def test_wkv6_carried_state():
    """Splitting a sequence across two kernel calls == one call (the decode
    / prefill continuation contract)."""
    B, S, H, hd = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    r, k, v = [_rand(ks[i], (B, S, H, hd), scale=0.5) for i in range(3)]
    w = jnp.exp(-jnp.exp(_rand(ks[3], (B, S, H, hd), scale=0.5)))
    u = jnp.zeros((H, hd))
    y_full, s_full = wkv6(r, k, v, w, u, interpret=True)
    y1, s1 = wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u,
                  interpret=True)
    y2, s2 = wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s0=s1,
                  interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=2e-4,
                               rtol=1e-3)


@settings(max_examples=8, deadline=None)
@given(B=st.integers(1, 2), S=st.sampled_from([64, 128]),
       D=st.sampled_from([128, 256]), N=st.sampled_from([4, 8]))
def test_mamba_scan_hypothesis(B, S, D, N):
    ks = jax.random.split(jax.random.PRNGKey(S + D + N), 5)
    u = _rand(ks[0], (B, S, D), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, D), scale=0.3))
    A = -jnp.exp(_rand(ks[2], (D, N), scale=0.3))
    Bi = _rand(ks[3], (B, S, N), scale=0.5)
    Ci = _rand(ks[4], (B, S, N), scale=0.5)
    y_k, h_k = mamba_scan(u, dt, A, Bi, Ci, chunk=32, d_block=128,
                          interpret=True)
    y_r, h_r = R.mamba_scan_ref(u, dt, A, Bi, Ci)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=2e-5,
                               rtol=1e-4)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(5, 200), d=st.integers(2, 6), T=st.sampled_from([10, 50]))
def test_gbm_predict_kernel_hypothesis(n, d, T):
    from repro.core.models.gbm import gbm_fit, gbm_predict as gbm_jnp
    rng = np.random.default_rng(n * d)
    X = rng.uniform(0, 10, (n, d)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1])).astype(np.float32)
    orders = jnp.asarray(np.argsort(X, axis=0).T)
    params = gbm_fit(jnp.asarray(X), jnp.asarray(y), jnp.ones(n), orders,
                     n_trees=T)
    ref = gbm_jnp(params, jnp.asarray(X))
    out = gbm_predict(jnp.asarray(X), params.feat, params.thr, params.leaf,
                      params.f0, params.y_scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


@pytest.fixture(scope="module")
def gbm_ensemble():
    """The pool GBM (200 trees, depth 3, log target) fitted on 4 features."""
    from repro.core.models.gbm import gbm_fit
    rng = np.random.default_rng(7)
    X = rng.uniform(0, 10, (96, 4)).astype(np.float32)
    y = (5 + X[:, 0] * 2 + np.sin(X[:, 1]) * X[:, 2]).astype(np.float32)
    orders = jnp.asarray(np.argsort(X, axis=0).T)
    return gbm_fit(jnp.asarray(X), jnp.asarray(y), jnp.ones(96), orders,
                   n_trees=200, depth=3, log_target=True)


@pytest.mark.parametrize("n", [1, 7, 255, 256, 1000, 1792, 4096])
def test_gbm_predict_kernel_row_counts(gbm_ensemble, n):
    """Every padding case — under one lane group, one block of up to 1024
    rows, several 1024-row blocks with a ragged tail — routes each row as
    the jnp path does and adds the same f32 leaves in the same order, so
    the raw ensemble sums are identical."""
    from repro.core.models.gbm import gbm_predict as gbm_jnp
    p = gbm_ensemble
    X = jnp.asarray(np.random.default_rng(n).uniform(-1, 11, (n, 4)),
                    jnp.float32)
    raw = gbm_predict(X, p.feat, p.thr, p.leaf, p.f0, interpret=True)
    ref = gbm_jnp(p._replace(y_scale=jnp.asarray(1.0)), X)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(ref))
