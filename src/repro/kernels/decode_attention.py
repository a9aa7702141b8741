"""Single-token GQA decode attention against a KV cache, blocked over the
cache length. Grid (B, KV, nkv) with the kv axis sequential; online-softmax
state in VMEM scratch. The prefix length (cache fill) arrives as a scalar in
SMEM so fully-masked tail blocks skip their matmuls.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, bkv: int, nkv: int, scale: float):
    j = pl.program_id(2)
    length = len_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_off = j * bkv

    @pl.when(k_off < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ki = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(ki < length, s, NEG_INF)     # (G, bkv)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     length: jnp.ndarray, *, block_kv: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, KV, G, D); caches: (B, KV, S, D); length: scalar int32 — number
    of valid cache positions. Returns (B, KV, G, D)."""
    B, KV, G, D = q.shape
    S = k_cache.shape[2]
    bkv = min(block_kv, S)
    assert S % bkv == 0
    nkv = S // bkv
    scale = 1.0 / math.sqrt(D)
    length = jnp.asarray(length, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, bkv=bkv, nkv=nkv, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j, *_: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, j, *_: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, q, k_cache, v_cache)
