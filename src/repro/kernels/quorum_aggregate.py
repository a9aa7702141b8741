"""RoCoIn quorum aggregation kernel: fused mask → concat → FC merge.

The source device aggregates the K student portions (some missing after
failures) and applies the FC head (paper Fig. 1 runtime phase). Fusing the
three steps means missing portions cost zero HBM traffic and the concat
buffer is never materialized:

    out (B, C) = Σ_k  mask_k · portion_k (B, Dk) @ W_k (Dk, C)   + bias

Grid (nb, K): K is sequential, the (bb, C) accumulator lives in scratch.
Portions are equal-width (planner pads partitions to a common width before
deployment — TPU-friendly layout).

int8 deployment mode: when ``weights`` is int8, pass per-slot fp32
``scales`` (K,) and the kernel dequantizes ``W_k`` in-body —
``W_k = q_k · scale_k`` — so HBM traffic for the merge weights drops 4x
and the fp32 expansion never leaves VMEM. The fp32 path multiplies by a
scale of 1.0, which is bit-exact, so both paths share one kernel body.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(mask_ref, scale_ref, p_ref, w_ref, b_ref, o_ref, acc_ref, *,
                K: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[k] != 0)
    def _accum():
        p = p_ref[0].astype(jnp.float32)           # (bb, Dk)
        # in-kernel dequant: int8 weights expand to fp32 in VMEM only
        w = w_ref[0].astype(jnp.float32) * scale_ref[k]   # (Dk, C)
        acc_ref[...] += jax.lax.dot_general(
            p, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == K - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] + b_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def quorum_aggregate(portions: jnp.ndarray, weights: jnp.ndarray,
                     bias: jnp.ndarray, mask: jnp.ndarray,
                     scales: Optional[jnp.ndarray] = None, *,
                     block_batch: int = 128, interpret: bool = False
                     ) -> jnp.ndarray:
    """portions: (K, B, Dk); weights: (K, Dk, C) fp32 or int8; bias: (C,);
    mask: (K,) int32 (1 = portion arrived); scales: optional (K,) fp32
    per-slot dequant scales (required when ``weights`` is int8).
    Returns logits (B, C)."""
    K, B, Dk = portions.shape
    C = weights.shape[-1]
    if weights.dtype == jnp.int8 and scales is None:
        raise ValueError("int8 weights need per-slot fp32 scales")
    if scales is None:
        scales = jnp.ones((K,), jnp.float32)
    if B == 0:
        # an empty batch would make bb = 0 and divide the grid by zero;
        # the merge of nothing is the empty logits block
        return jnp.zeros((0, C), jnp.float32)
    # ragged guard: legal grid for any block; a tile smaller than the batch
    # is a multiple of 8 rows (the TPU's second-to-last block constraint)
    bb = max(1, min(block_batch, B))
    if bb < B:
        bb = -(-bb // 8) * 8
    pad = (-B) % bb
    if pad:
        portions = jnp.pad(portions, ((0, 0), (0, pad), (0, 0)))
    nb = portions.shape[1] // bb

    kernel = functools.partial(_agg_kernel, K=K)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb, K),
        in_specs=[
            pl.BlockSpec((1, bb, Dk), lambda i, k, *_: (k, i, 0)),
            pl.BlockSpec((1, Dk, C), lambda i, k, *_: (k, 0, 0)),
            pl.BlockSpec((C,), lambda i, k, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((bb, C), lambda i, k, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bb, C), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((portions.shape[1], C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(mask, jnp.int32), jnp.asarray(scales, jnp.float32),
      portions, weights, bias)
    return out[:B]
