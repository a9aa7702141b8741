"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
           dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,KV,G,S,D", [(1, 1, 1, 128, 64), (2, 2, 4, 256, 64),
                                        (1, 4, 2, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, KV, G, S, D, dtype, causal):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, KV, G, S, D), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, D), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,KV,G,S,D", [(2, 2, 4, 256, 64), (1, 1, 8, 512, 128)])
@pytest.mark.parametrize("length", [1, 100, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, KV, G, S, D, length, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, KV, G, D), dtype)
    kc = jax.random.normal(ks[1], (B, KV, S, D), dtype)
    vc = jax.random.normal(ks[2], (B, KV, S, D), dtype)
    out = ops.decode_attention(q, kc, vc, jnp.int32(length), block_kv=128)
    exp = ref.decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("BH,L,P,N,chunk", [(2, 64, 16, 16, 16),
                                            (3, 128, 32, 64, 32),
                                            (1, 256, 64, 128, 64)])
def test_ssd_scan(BH, L, P, N, chunk):
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (BH, L, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (BH, L)))
    A = -jnp.exp(jax.random.normal(ks[2], (BH,)))
    Bm = jax.random.normal(ks[3], (BH, L, N))
    Cm = jax.random.normal(ks[4], (BH, L, N))
    out = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    exp = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-3, rtol=2e-3)


def test_ssd_scan_matches_model_chunked():
    """Kernel must agree with the model's ssd_chunked (the lowered path)."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.key(5), 5)
    B, L, H, P, N = 2, 64, 3, 16, 32
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    y_model, _ = ssd_chunked(x, dt, A, Bm, Cm, 16)
    # kernel layout: (B*H, L, ·) with per-head A and per-head dt
    xk = x.transpose(0, 2, 1, 3).reshape(B * H, L, P)
    dtk = dt.transpose(0, 2, 1).reshape(B * H, L)
    Ak = jnp.tile(A, B)
    Bk = jnp.repeat(Bm, H, axis=0)
    Ck = jnp.repeat(Cm, H, axis=0)
    y_k = ops.ssd_scan(xk, dtk, Ak, Bk, Ck, chunk=16)
    y_k = y_k.reshape(B, H, L, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_model, np.float32),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape", [(4, 64, 256), (1, 7, 512), (2, 100, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    ks = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    sc = jax.random.normal(ks[1], (shape[-1],), dtype)
    out = ops.rmsnorm(x, sc, block_rows=32)
    exp = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("K,B,Dk,C", [(4, 64, 32, 10), (8, 100, 16, 100),
                                      (1, 32, 64, 10)])
def test_quorum_aggregate(K, B, Dk, C):
    ks = jax.random.split(jax.random.key(4), 4)
    p = jax.random.normal(ks[0], (K, B, Dk))
    w = jax.random.normal(ks[1], (K, Dk, C))
    b = jax.random.normal(ks[2], (C,))
    mask = (jax.random.uniform(ks[3], (K,)) > 0.3).astype(jnp.int32)
    out = ops.quorum_aggregate(p, w, b, mask, block_batch=32)
    exp = ref.quorum_aggregate_ref(p, w, b, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


def test_quorum_all_failed_is_bias():
    p = jnp.ones((3, 8, 4))
    w = jnp.ones((3, 4, 5))
    b = jnp.arange(5.0)
    out = ops.quorum_aggregate(p, w, b, jnp.zeros(3, jnp.int32))
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.arange(5.0), (8, 5)))


@pytest.mark.parametrize("B,R,K,F", [(5, 6, 4, 16), (128, 3, 2, 8),
                                     (1, 9, 7, 32)])
def test_coded_decode(B, R, K, F):
    ks = jax.random.split(jax.random.key(7), 3)
    sh = jax.random.normal(ks[0], (R, B, F))
    dec = jax.random.normal(ks[1], (K, B, R))
    mask = (jax.random.uniform(ks[2], (B, R)) > 0.3).astype(jnp.int32)
    out = ops.coded_decode(sh, dec, mask, block_batch=32)
    exp = ref.coded_decode_ref(sh, dec, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("B,R,K,F", [(16, 5, 3, 16), (64, 4, 4, 8)])
def test_coded_decode_int8_shares(B, R, K, F):
    ks = jax.random.split(jax.random.key(8), 3)
    sh = jax.random.randint(ks[0], (R, B, F), -127, 128, jnp.int8)
    dec = jax.random.normal(ks[1], (K, B, R))
    mask = (jax.random.uniform(ks[2], (B, R)) > 0.4).astype(jnp.int32)
    scales = jnp.abs(jax.random.normal(jax.random.key(9), (R,))) + 0.1
    out = ops.coded_decode(sh, dec, mask, scales, block_batch=32)
    exp = ref.coded_decode_ref(sh, dec, mask, scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


def test_coded_decode_int8_needs_scales():
    with pytest.raises(ValueError, match="scales"):
        ops.coded_decode(jnp.zeros((3, 2, 4), jnp.int8),
                         jnp.zeros((2, 2, 3)), jnp.ones((2, 3), jnp.int32))


def test_coded_decode_dead_shares_contribute_nothing():
    """An all-dead mask yields exactly zero regardless of share payloads."""
    sh = jnp.ones((3, 4, 8)) * 1e6
    dec = jnp.ones((2, 4, 3))
    out = ops.coded_decode(sh, dec, jnp.zeros((4, 3), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.zeros((2, 4, 8)))


@pytest.mark.parametrize("N,E,k", [(128, 8, 2), (1000, 64, 6), (77, 16, 4)])
def test_topk_gating(N, E, k):
    lg = jax.random.normal(jax.random.key(6), (N, E))
    w1, i1 = ops.topk_gating(lg, k, block_rows=64)
    w2, i2 = ref.topk_gating_ref(lg, k)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               atol=1e-5, rtol=1e-5)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    # weights renormalized
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, atol=1e-5)
