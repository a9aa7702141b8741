"""Fused coded-share decode: masked pseudo-inverse-weighted gather-matmul.

After an erasure-coded dispatch the source holds the arrived-share tensor
(R, B, F) — R = K systematic + P parity shares, rows of dead shares
garbage — and a per-request decode operator ``dec`` (K, B, R) built host-side
from the arrival pattern (identity rows for arrived systematic shares,
pseudo-inverse rows of the MDS generator for erased ones, zeros for
unrecoverable slots — see :func:`repro.coding.codes.decode_matrix`). The
kernel fuses mask → (optional int8 share dequant) → per-request weighted
gather over the share axis into one pass, so dead-share rows cost no HBM
traffic re-reads and the recovered portion tensor never materializes an
intermediate:

    out (K, B, F)[k, b] = Σ_r  mask[b, r] · dec[k, b, r] · share[r, b] · s_r

The share axis leads and the batch axis sits second-to-last in every
operand, so each block's last two dims are (batch tile, full width) — the
layout the TPU lowering requires — and the output is already in the
(K, B, F) slot-major layout the quorum merge consumes.

Grid (nb, K), both parallel: each program reduces the full (small) share
axis for one (batch-tile, slot) pair on the VPU — R is a handful of shares,
so the reduction is a short broadcast-multiply-accumulate, not a matmul.

int8 transport mode: when ``shares`` is int8 (quantized share uplinks), pass
per-share fp32 ``scales`` (R,) and the kernel dequantizes in-body — the fp32
expansion of the share payload lives only in VMEM. The fp32 path multiplies
by a scale of 1.0, which is bit-exact, so both paths share one kernel body.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _decode_kernel(x_ref, d_ref, m_ref, s_ref, o_ref):
    # fold mask and dequant scale into the per-request decode weights: one
    # (bb, R) weight row instead of touching the (R, bb, F) payload twice
    w = d_ref[...] * m_ref[...].astype(jnp.float32) * s_ref[...]   # (bb, R)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for r in range(x_ref.shape[0]):
        acc = acc + w[:, r:r + 1] * x_ref[r].astype(jnp.float32)
    o_ref[...] = acc


def coded_decode(shares: jnp.ndarray, dec: jnp.ndarray, mask: jnp.ndarray,
                 scales: Optional[jnp.ndarray] = None, *,
                 block_batch: int = 128, interpret: bool = False
                 ) -> jnp.ndarray:
    """shares: (R, B, F) fp32 or int8 arrived-share tensor; dec: (K, B, R)
    fp32 per-request decode weights; mask: (B, R) share-arrival mask;
    scales: optional (R,) fp32 per-share dequant scales (required when
    ``shares`` is int8). Returns the recovered portions (K, B, F) fp32."""
    R, B, F = shares.shape
    K = dec.shape[0]
    if shares.dtype == jnp.int8 and scales is None:
        raise ValueError("int8 shares need per-share fp32 scales")
    if scales is None:
        scales = jnp.ones((R,), jnp.float32)
    if B == 0:
        return jnp.zeros((K, 0, F), jnp.float32)
    # ragged guard: legal grid for any block; a tile smaller than the batch
    # is a multiple of 8 rows (the TPU's second-to-last block constraint)
    bb = max(1, min(block_batch, B))
    if bb < B:
        bb = -(-bb // 8) * 8
    pad = (-B) % bb
    if pad:
        shares = jnp.pad(shares, ((0, 0), (0, pad), (0, 0)))
        dec = jnp.pad(dec, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    nb = shares.shape[1] // bb

    out = pl.pallas_call(
        _decode_kernel,
        grid=(nb, K),
        in_specs=[
            pl.BlockSpec((R, bb, F), lambda i, k: (0, i, 0)),
            pl.BlockSpec((None, bb, R), lambda i, k: (k, i, 0)),
            pl.BlockSpec((bb, R), lambda i, k: (i, 0)),
            pl.BlockSpec((1, R), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bb, F), lambda i, k: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, shares.shape[1], F), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(shares, jnp.asarray(dec, jnp.float32), jnp.asarray(mask, jnp.int32),
      jnp.asarray(scales, jnp.float32).reshape(1, R))
    return out[:, :B]
