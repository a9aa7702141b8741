"""Blocked causal GQA prefill attention (flash-attention style) for TPU.

Grid (B, KV, nq, nkv); the last axis is sequential ("arbitrary") so the
online-softmax state lives in VMEM scratch across kv blocks. Each grid cell
processes one (batch, kv-head) pair, a q block of G grouped query heads, and
one kv block:

    m, l, acc ← online softmax update with the (G·bq × bkv) score tile.

BlockSpecs stage q/k/v tiles in VMEM; the MXU sees (G·bq, D)×(D, bkv) and
(G·bq, bkv)×(bkv, D) matmuls with D, bkv multiples of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bkv: int, causal: bool, scale: float, nkv: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, bq, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (bkv, D)
    v = v_ref[0, 0].astype(jnp.float32)           # (bkv, D)
    G, bq_, D = q.shape

    i = pl.program_id(2)
    q_off = i * bq
    k_off = j * bkv

    run = True
    if causal:
        run = (k_off <= q_off + bq - 1)

    @pl.when(run if causal else True)
    def _compute():
        s = jax.lax.dot_general(q.reshape(G * bq_, D), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s.reshape(G, bq_, bkv)
        if causal:
            qi = q_off + jax.lax.broadcasted_iota(jnp.int32, (G, bq_, bkv), 1)
            ki = k_off + jax.lax.broadcasted_iota(jnp.int32, (G, bq_, bkv), 2)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]                        # (G, bq)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])          # (G, bq, bkv)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.reshape(G * bq_, bkv), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv.reshape(G, bq_, D)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, interpret: bool = False
                    ) -> jnp.ndarray:
    """q: (B, KV, G, Sq, D); k, v: (B, KV, Skv, D) → (B, KV, G, Sq, D)."""
    B, KV, G, Sq, D = q.shape
    Skv = k.shape[2]
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)
    nq, nkv = Sq // bq, Skv // bkv
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, bq=bq, bkv=bkv, causal=causal,
                               scale=scale, nkv=nkv)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, D), lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq, D), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
