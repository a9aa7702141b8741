"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True) -> jnp.ndarray:
    """q: (B, KV, G, Sq, D); k, v: (B, KV, Skv, D)."""
    B, KV, G, Sq, D = q.shape
    Skv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhgqd,bhsd->bhgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(Sq)[:, None]
        ki = jnp.arange(Skv)[None, :]
        s = jnp.where((ki <= qi)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqs,bhsd->bhgqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, length) -> jnp.ndarray:
    """q: (B, KV, G, D); caches: (B, KV, S, D); length: scalar."""
    B, KV, G, D = q.shape
    S = k_cache.shape[2]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, None, :] < length
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)


def ssd_scan_ref(x, dt, A, Bm, Cm) -> jnp.ndarray:
    """Sequential (non-chunked) SSD recurrence — the ground truth.
    x: (BH, L, P); dt: (BH, L); A: (BH,); Bm/Cm: (BH, L, N)."""
    BH, L, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf, Cf = Bm.astype(jnp.float32), Cm.astype(jnp.float32)

    def per_seq(xs, dts, a, bs, cs):
        def step(h, args):
            xt, dtt, bt, ct = args
            decay = jnp.exp(dtt * a)
            h = h * decay + jnp.outer(xt * dtt, bt)     # (P, N)
            y = h @ ct                                   # (P,)
            return h, y
        h0 = jnp.zeros((P, N), jnp.float32)
        _, ys = jax.lax.scan(step, h0, (xs, dts, bs, cs))
        return ys

    ys = jax.vmap(per_seq)(xf, dtf, A.astype(jnp.float32), Bf, Cf)
    return ys.astype(x.dtype)


def rmsnorm_ref(x, scale, *, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def quorum_aggregate_ref(portions, weights, bias, mask,
                         scales=None) -> jnp.ndarray:
    """portions: (K, B, Dk); weights: (K, Dk, C) fp32 or int8; bias: (C,);
    mask: (K,); scales: optional (K,) per-slot dequant scales."""
    m = mask.astype(jnp.float32)[:, None, None]
    w = weights.astype(jnp.float32)
    if scales is not None:
        w = w * scales.astype(jnp.float32)[:, None, None]
    out = jnp.einsum("kbd,kdc->bc", portions.astype(jnp.float32) * m, w)
    return out + bias.astype(jnp.float32)


def coded_decode_ref(shares, dec, mask, scales=None) -> jnp.ndarray:
    """shares: (R, B, F) fp32 or int8; dec: (K, B, R); mask: (B, R);
    scales: optional (R,) per-share dequant scales. Returns (K, B, F)."""
    w = dec.astype(jnp.float32) * mask.astype(jnp.float32)[None, :, :]
    if scales is not None:
        w = w * scales.astype(jnp.float32)[None, None, :]
    return jnp.einsum("kbr,rbf->kbf", w, shares.astype(jnp.float32))


def coded_matmul_ref(x, shards) -> jnp.ndarray:
    """x: (B, D); shards: (n, D, w) stacked compute-shard weights.
    Returns the (n, B, w) per-shard partial products ``x @ shards[i]``."""
    return jnp.einsum("bd,ndw->nbw", x.astype(jnp.float32),
                      shards.astype(jnp.float32))


def dequant_matmul_ref(x, q, scale) -> jnp.ndarray:
    """x: (B, D); q: (D, N) int8; scale: () or (N,) fp32."""
    w = q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    return x.astype(jnp.float32) @ w


def topk_gating_ref(logits, k):
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, i = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, i.astype(jnp.int32)
