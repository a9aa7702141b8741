"""Fused weight-dequant matmul: ``y = x @ (q · scale)`` with int8 ``q``.

The memory-bound half of RoCoIn's edge portions is the weight stream; weight
-only int8 quantization (per-tensor or per-output-channel fp32 scale) cuts
that HBM traffic 4x. Fusing the dequant into the matmul means the fp32
expansion of the weight lives only in VMEM — the int8 bytes are what moves.

Grid (nb, nn): rows × output-column tiles, the full reduction dim D in one
block (RoCoIn portion widths are small; tile D before raising it past VMEM).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dqmm_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # (bb, D)
    w = q_ref[...].astype(jnp.float32) * s_ref[...]  # (D, bn) · (1, bn)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def dequant_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
                   block_batch: int = 128, block_n: int = 256,
                   interpret: bool = False) -> jnp.ndarray:
    """x: (B, D) fp32; q: (D, N) int8; scale: () per-tensor or (N,)
    per-output-channel fp32. Returns (B, N) fp32.

    The scale travels as a (1, N) row tiled with the output columns (a
    per-tensor scale is broadcast to that row first), so both forms share
    one kernel body and one VMEM block per column tile."""
    B, D = x.shape
    N = q.shape[-1]
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (N,))
    if B == 0:
        return jnp.zeros((0, N), jnp.float32)
    # ragged-tile guard: clamp blocks into [1, dim] (an oversized or
    # non-positive block — e.g. a stale tuning-table entry — must degrade to
    # a legal grid, not a zero-division or a negative pad), then pad the
    # last tile up to a full block; the pad rows/cols are sliced off below.
    # A tile smaller than its dim is rounded up to the TPU tiling (8 rows,
    # 128 columns)
    bb = max(1, min(block_batch, B))
    if bb < B:
        bb = -(-bb // 8) * 8
    bn = max(1, min(block_n, N))
    if bn < N:
        bn = -(-bn // 128) * 128
    pad_b, pad_n = (-B) % bb, (-N) % bn
    if pad_b:
        x = jnp.pad(x, ((0, pad_b), (0, 0)))
    if pad_n:
        q = jnp.pad(q, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale, (0, pad_n))
    nb, nn = x.shape[0] // bb, q.shape[1] // bn

    out = pl.pallas_call(
        _dqmm_kernel,
        grid=(nb, nn),
        in_specs=[
            pl.BlockSpec((bb, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], q.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, q, scale.reshape(1, -1))
    return out[:B, :N]
