"""Plain ``jax.numpy`` pieces that every kind's reference shares.

Imports nothing of the program. ``drawn`` hands out a network's weights
from the seed in a fixed order; ``merge`` is what a quorum answer has to
be, given every slot's portion of the teacher's knowledge:

    logits = bias + sum over arrived slots k of  portion_k(x) @ W_k

where ``W_k`` are the merge head's rows of slot k's portion. What a
portion is, and how it is computed, is the kind's (``bench/kinds/``).
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class _Draw:
    """Hands out the weights of a network from two flat random vectors, one
    standard normal and one uniform on [0, 1), in a fixed order. A first
    pass without vectors only counts; so a whole ensemble takes two random
    draws, which keeps its jitted initialisation small to compile."""

    def __init__(self, normal=None, uniform=None):
        self.normal, self.uniform = normal, uniform
        self.n = self.u = 0

    def _take(self, flat, at: int, shape):
        size = int(np.prod(shape))
        if flat is None:
            return jnp.zeros(shape), at + size
        return flat[at:at + size].reshape(shape), at + size

    def gauss(self, shape, std):
        x, self.n = self._take(self.normal, self.n, shape)
        return std * x

    def unif(self, shape, lo, hi):
        x, self.u = self._take(self.uniform, self.u, shape)
        return lo + (hi - lo) * x


def drawn(key, build):
    """``build(draw)`` with its weights drawn from ``key``: ``draw.gauss``
    and ``draw.unif`` give each weight in the order ``build`` asks."""
    count = _Draw()
    build(count)
    k1, k2 = jax.random.split(key)
    return build(_Draw(jax.random.normal(k1, (count.n,)),
                       jax.random.uniform(k2, (count.u,))))


def merge(weights: Dict, slots: Sequence[tuple], feats: np.ndarray,
          row_mask: np.ndarray, *, dtype=jnp.float32,
          precision: str = "highest") -> np.ndarray:
    """Quorum merge of ``feats`` (K, B, Dk) under the per-row arrived mask
    ``row_mask`` (B, K): (B, C) logits. ``weights["fc"]`` holds the head's
    ``kernel`` (sum of widths, C), slot after slot, and ``bias`` (C,)."""
    kernel = weights["fc"]["kernel"]
    offs = np.concatenate([[0], np.cumsum([w for _, w in slots])])
    Dk = feats.shape[2]
    W = jnp.stack([jnp.pad(kernel[offs[k]:offs[k + 1]],
                           ((0, Dk - (offs[k + 1] - offs[k])), (0, 0)))
                   for k in range(len(slots))])
    with jax.default_matmul_precision(precision):
        f = jnp.asarray(feats, dtype) * jnp.asarray(
            row_mask.T[:, :, None], dtype)
        out = jnp.einsum("kbd,kdc->bc", f, W.astype(dtype)) \
            + weights["fc"]["bias"].astype(dtype)
    return np.asarray(out.astype(jnp.float32))
