"""Mamba2 SSD chunked scan kernel (state-space duality, arXiv:2405.21060).

Grid (B·H, n_chunks); the chunk axis is sequential, the running state
(P × N, f32) lives in VMEM scratch across chunks. Per chunk the kernel
computes the intra-chunk dual quadratic form (two MXU matmuls + decay mask)
and the inter-chunk contribution from the carried state — exactly the
reference ``repro.models.ssm.ssd_chunked`` recurrence:

    y[t] = Σ_{s≤t} C_t·B_s · exp(cum_t − cum_s) · x_s·dt_s  +  C_t·(h·exp(cum_t))
    h'   = h · exp(cum_Q)  +  Σ_s exp(cum_Q − cum_s) · B_s (x_s·dt_s)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                Q: int, nc: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)         # (Q,)
    A = a_ref[0]                                  # scalar (per head), negative
    Bm = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)          # (Q, N)

    la = dt * A                                   # (Q,) log-decay ≤ 0
    cum = jnp.cumsum(la)                          # (Q,)
    xb = x * dt[:, None]                          # dt folded into x

    # intra-chunk: scores[t,s] = C_t·B_s · exp(cum_t − cum_s), t ≥ s
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    decay = jnp.exp(cum[:, None] - cum[None, :])
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    scores = jnp.where(ti >= si, scores * decay, 0.0)
    y_intra = jax.lax.dot_general(scores, xb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)

    # inter-chunk from carried state h (P,N): y_inter[t] = (C_t·h^T)·exp(cum_t)
    h = h_ref[...]
    y_inter = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)
    y_inter = y_inter * jnp.exp(cum)[:, None]

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = h·exp(cum_Q) + Σ_s exp(cum_Q − cum_s) xb_s ⊗ B_s
    last = cum[Q - 1]
    sdecay = jnp.exp(last - cum)                  # (Q,)
    xs = xb * sdecay[:, None]                     # (Q, P)
    upd = jax.lax.dot_general(xs, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = h * jnp.exp(last) + upd


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, Bm: jnp.ndarray,
             Cm: jnp.ndarray, *, chunk: int = 128, interpret: bool = False
             ) -> jnp.ndarray:
    """x: (BH, L, P); dt: (BH, L); A: (BH,) negative per-head decay;
    Bm, Cm: (BH, L, N). Returns y (BH, L, P)."""
    BH, L, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0
    nc = L // Q
    x4 = x.reshape(BH, nc, Q, P)
    dt3 = dt.reshape(BH, nc, Q)
    B4 = Bm.reshape(BH, nc, Q, N)
    C4 = Cm.reshape(BH, nc, Q, N)

    kernel = functools.partial(_ssd_kernel, Q=Q, nc=nc)
    y = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nc, Q, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x4, dt3, A.astype(jnp.float32), B4, C4)
    return y.reshape(BH, L, P)
