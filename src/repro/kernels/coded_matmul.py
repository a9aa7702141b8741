"""Coded shard matmul: one MDS compute shard's partial product per program.

Intermediate-computation coding (`repro.coding.compute`) splits a portion's
final linear layer ``y = x @ W`` into ``k`` output-column blocks and adds
``r = n - k`` pre-encoded parity blocks ``W~_j = Σ_i G[j, i] · W_i``, so each
of ``n`` devices runs the SAME small matmul against its own ``(D, w)`` shard
weight and any ``k`` arrivals reconstruct ``y`` exactly.  This kernel is the
device-side primitive: given the stacked shard weights ``(n, D, w)`` it
computes every shard's partial product ``x @ W_i`` in one launch —

    out (n, B, w)[i] = x (B, D) @ shards (n, D, w)[i]

Grid (n, nb), both parallel: program (i, b) runs one batch tile of shard
``i`` on the MXU.  The reduction dim D stays whole per block (portion widths
are small); ``preferred_element_type=float32`` keeps the accumulator fp32 so
systematic shard outputs are bit-identical to the corresponding column block
of the uncoded matmul — the passthrough the cancel-on-first-k serving path
relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _shard_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                   # (bb, D)
    w = w_ref[0].astype(jnp.float32)                     # (D, w)
    o_ref[0] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def coded_matmul(x: jnp.ndarray, shards: jnp.ndarray, *,
                 block_batch: int = 128, interpret: bool = False
                 ) -> jnp.ndarray:
    """x: (B, D) fp32 activations; shards: (n, D, w) stacked shard weights
    from :func:`repro.coding.compute.shard_linear_weights` (systematic rows
    first). Returns the (n, B, w) fp32 per-shard partial products."""
    B, D = x.shape
    n, _, w = shards.shape
    if B == 0:
        return jnp.zeros((n, 0, w), jnp.float32)
    bb = min(block_batch, B)
    pad = (-B) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nb = x.shape[0] // bb

    out = pl.pallas_call(
        _shard_kernel,
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec((bb, D), lambda s, i: (i, 0)),
            pl.BlockSpec((1, D, w), lambda s, i: (s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bb, w), lambda s, i: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, x.shape[0], w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, shards)
    return out[:, :B]
