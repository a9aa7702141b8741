"""Public jit'd wrappers for the Pallas kernels.

On CPU (this container) kernels execute in interpret mode — the kernel body
runs in Python for correctness validation; on TPU the same call compiles to
Mosaic. `interpret=None` auto-detects.

The serving-path wrappers (`quorum_aggregate`, `coded_decode`,
`dequant_matmul`) resolve their block sizes through the autotuner's
shape-keyed tuning table (:mod:`repro.kernels.autotune`) when the caller
does not pin them: pass ``block_batch=None`` (the default) and the table
entry for this problem shape wins, falling back to the historical defaults
on a miss. Resolution happens in a thin non-jitted shim — shapes and dtypes
are static even on tracers, so the lookup is trace-safe and the inner jitted
kernels see only concrete static block sizes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import coded_decode as _cd
from repro.kernels import coded_matmul as _cm
from repro.kernels import decode_attention as _dec
from repro.kernels import dequant_matmul as _dq
from repro.kernels import flash_attention as _fa
from repro.kernels import quorum_aggregate as _qa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_gating as _tg


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, interpret: Optional[bool] = None):
    """GQA prefill attention. q: (B, KV, G, Sq, D); k/v: (B, KV, Skv, D)."""
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv,
                               interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, block_kv: int = 256,
                     interpret: Optional[bool] = None):
    """One-token GQA decode. q: (B, KV, G, D); caches: (B, KV, S, D)."""
    return _dec.decode_attention(q, k_cache, v_cache, length,
                                 block_kv=block_kv,
                                 interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """Mamba2 chunked scan. x: (BH, L, P); dt: (BH, L); A: (BH,);
    Bm/Cm: (BH, L, N)."""
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: Optional[bool] = None):
    """Root-mean-square layer norm over the last axis, scaled by ``scale``."""
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def _quorum_aggregate_jit(portions, weights, bias, mask, scales, *,
                          block_batch: int, interpret: Optional[bool]):
    return _qa.quorum_aggregate(portions, weights, bias, mask, scales,
                                block_batch=block_batch,
                                interpret=_auto_interpret(interpret))


def quorum_aggregate(portions, weights, bias, mask, scales=None, *,
                     block_batch: Optional[int] = None,
                     interpret: Optional[bool] = None):
    """Fused masked-concat + FC merge of student portions (RoCoIn runtime).
    Pass int8 ``weights`` with per-slot fp32 ``scales`` (K,) for the
    quantized-deployment merge (dequant happens in-kernel).
    ``block_batch=None`` consults the autotuning table for this shape."""
    shape, dtype = _at.key_quorum_aggregate(portions, weights)
    blocks = _at.resolve("quorum_aggregate", shape, dtype,
                         {"block_batch": block_batch})
    return _quorum_aggregate_jit(portions, weights, bias, mask, scales,
                                 block_batch=blocks["block_batch"],
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def _coded_decode_jit(shares, dec, mask, scales, *, block_batch: int,
                      interpret: Optional[bool]):
    return _cd.coded_decode(shares, dec, mask, scales,
                            block_batch=block_batch,
                            interpret=_auto_interpret(interpret))


def coded_decode(shares, dec, mask, scales=None, *,
                 block_batch: Optional[int] = None,
                 interpret: Optional[bool] = None):
    """Fused masked decode of erasure-coded shares (coding subsystem).
    shares: (R, B, F) arrived-share tensor (fp32 or int8 with per-share
    ``scales``); dec: (K, B, R) per-request decode weights; mask: (B, R).
    Returns the recovered portions (K, B, F).
    ``block_batch=None`` consults the autotuning table for this shape."""
    shape, dtype = _at.key_coded_decode(shares, dec)
    blocks = _at.resolve("coded_decode", shape, dtype,
                         {"block_batch": block_batch})
    return _coded_decode_jit(shares, dec, mask, scales,
                             block_batch=blocks["block_batch"],
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_batch", "interpret"))
def coded_matmul(x, shards, *, block_batch: int = 128,
                 interpret: Optional[bool] = None):
    """Per-shard partial products for intermediate-computation coding.
    x: (B, D) fp32 activations; shards: (n, D, w) stacked shard weights from
    :func:`repro.coding.compute.shard_linear_weights` (systematic first).
    Returns (n, B, w) fp32 — any k rows reconstruct ``x @ W`` exactly."""
    return _cm.coded_matmul(x, shards, block_batch=block_batch,
                            interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_batch", "block_n",
                                             "interpret"))
def _dequant_matmul_jit(x, q, scale, *, block_batch: int, block_n: int,
                        interpret: Optional[bool]):
    return _dq.dequant_matmul(x, q, scale, block_batch=block_batch,
                              block_n=block_n,
                              interpret=_auto_interpret(interpret))


def dequant_matmul(x, q, scale, *, block_batch: Optional[int] = None,
                   block_n: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Fused weight-dequant matmul ``x @ (q · scale)`` — int8 weights, fp32
    activations (weight-only quantized portion forwards).
    ``block_batch=None`` / ``block_n=None`` consult the autotuning table."""
    shape, dtype = _at.key_dequant_matmul(x, q)
    blocks = _at.resolve("dequant_matmul", shape, dtype,
                         {"block_batch": block_batch, "block_n": block_n})
    return _dequant_matmul_jit(x, q, scale,
                               block_batch=blocks["block_batch"],
                               block_n=blocks["block_n"],
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def topk_gating(logits, k: int, *, block_rows: int = 512,
                interpret: Optional[bool] = None):
    """MoE router: fused softmax + top-k + renormalize."""
    return _tg.topk_gating(logits, k, block_rows=block_rows,
                           interpret=_auto_interpret(interpret))
