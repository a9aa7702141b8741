"""Plain ``jax.numpy`` reference of a served RoCoIn ensemble.

Imports nothing of the program. It knows the architectures from the
configuration file (``archs``), makes the weights from the seed, and
computes what a quorum answer has to be:

    logits = bias + sum over arrived slots k of  features_k(x) @ W_k

where ``features_k`` is student k's pooled final-conv activity (its
knowledge portion, of the slot's width) and ``W_k`` are the FC rows of that
portion. The parameter layout is the usual one for these networks (per
block: ``bn1``/``conv1``/``bn2``/``conv2``/``shortcut`` for a pre-activation
WRN block; ``expand``/``bn0``/``dw``/``bn1``/``project``/``bn2`` for an
inverted residual), so the harness can hand the same arrays to the program.

Conventions that the networks' papers leave open are fixed here as the
configuration states them: "SAME" padding (a strided 3x3 pads one row and
column after the image), a strided identity shortcut subsamples, and
batch norm runs in inference mode with eps 1e-5.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5


# ---------------------------------------------------------------------------
# architectures
# ---------------------------------------------------------------------------

def arch_spec(archs: Dict, name: str) -> Dict:
    """The configuration's description of the architecture ``name``."""
    if name not in archs:
        raise KeyError(f"architecture {name!r} is not described in the "
                       f"configuration's 'archs'")
    return archs[name]


def wrn_stages(spec: Dict, width: int) -> List[tuple]:
    """(channels, stride) of every block of a WRN whose last group is
    ``width`` wide."""
    n = (spec["depth"] - 4) // 6
    widths = [16 * spec["widen"], 32 * spec["widen"], width]
    return [(widths[g], (1 if g == 0 else 2) if b == 0 else 1)
            for g in range(3) for b in range(n)]


def mbv2_stages(spec: Dict) -> List[tuple]:
    """(expansion, channels, stride) of every inverted-residual block."""
    out = []
    for exp, ch, n, stride in spec["blocks"]:
        out += [(exp, ch, stride if i == 0 else 1) for i in range(n)]
    return out


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

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


def _drawn(key, build):
    """``build(draw)`` with its weights drawn from ``key``."""
    count = _Draw()
    build(count)
    k1, k2 = jax.random.split(key)
    return build(_Draw(jax.random.normal(k1, (count.n,)),
                       jax.random.uniform(k2, (count.u,))))


def _conv(draw, k, cin, cout):
    return {"kernel": draw.gauss((k, k, cin, cout), np.sqrt(2.0 / (k * k * cin)))}


def _bn(draw, ch):
    return {"scale": draw.unif((ch,), 0.8, 1.2),
            "bias": draw.gauss((ch,), 0.05),
            "mean": draw.gauss((ch,), 0.05),
            "var": draw.unif((ch,), 0.8, 1.2)}


def _unused_head(cin, n_classes):
    # the students' own classifier is not part of a served portion
    return {"kernel": jnp.zeros((cin, n_classes)),
            "bias": jnp.zeros((n_classes,))}


def _student(draw, spec: Dict, width: int, n_classes: int,
             in_ch: int = 3) -> Dict:
    if spec["kind"] == "wrn":
        p = {"conv0": _conv(draw, 3, in_ch, 16)}
        cin = 16
        n = (spec["depth"] - 4) // 6
        for i, (cout, _) in enumerate(wrn_stages(spec, width)):
            blk = {"bn1": _bn(draw, cin),
                   "conv1": _conv(draw, 3, cin, cout),
                   "bn2": _bn(draw, cout),
                   "conv2": _conv(draw, 3, cout, cout)}
            if cin != cout:
                blk["shortcut"] = _conv(draw, 1, cin, cout)
            p[f"g{i // n}b{i % n}"] = blk
            cin = cout
        p["bn_out"] = _bn(draw, cin)
        p["fc"] = _unused_head(cin, n_classes)
        return p
    if spec["kind"] == "mbv2":
        stem = spec["stem"]
        p = {"conv0": _conv(draw, 3, in_ch, stem), "bn0": _bn(draw, stem)}
        cin = stem
        for i, (exp, cout, _) in enumerate(mbv2_stages(spec)):
            mid = cin * exp
            p[f"b{i}"] = {
                "expand": _conv(draw, 1, cin, mid) if exp != 1 else None,
                "bn0": _bn(draw, mid),
                "dw": {"kernel": draw.gauss((3, 3, 1, mid), np.sqrt(2.0 / 9))},
                "bn1": _bn(draw, mid),
                "project": _conv(draw, 1, mid, cout),
                "bn2": _bn(draw, cout)}
            cin = cout
        p["conv_last"] = _conv(draw, 1, cin, width)
        p["bn_last"] = _bn(draw, width)
        p["fc"] = _unused_head(width, n_classes)
        return p
    raise KeyError(f"unknown architecture kind {spec['kind']!r}")


def init_student(key, spec: Dict, width: int, n_classes: int,
                 in_ch: int = 3) -> Dict:
    """Random weights of one student whose portion is ``width`` wide."""
    return _drawn(key, lambda d: _student(d, spec, width, n_classes, in_ch))


def init_ensemble(key, archs: Dict, slots: Sequence[tuple],
                  n_classes: int) -> Dict:
    """Every slot's student plus the FC merge head. ``slots`` is a tuple of
    (arch name, width) pairs; jit this with ``slots`` static."""
    total = sum(w for _, w in slots)

    def build(draw):
        students = [_student(draw, arch_spec(archs, a), w, n_classes)
                    for a, w in slots]
        kernel = draw.gauss((total, n_classes), 1 / np.sqrt(total))
        bias = draw.gauss((n_classes,), 0.1)
        return {"students": students, "fc": {"kernel": kernel, "bias": bias}}
    return _drawn(key, build)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _conv_apply(p, x, stride=1, groups=1):
    w = p["kernel"].astype(x.dtype)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _bn_apply(p, x):
    dt = x.dtype
    inv = jax.lax.rsqrt(p["var"].astype(dt) + jnp.asarray(BN_EPS, dt))
    return (x - p["mean"].astype(dt)) * inv * p["scale"].astype(dt) \
        + p["bias"].astype(dt)


def features(p: Dict, spec: Dict, width: int, x: jnp.ndarray) -> jnp.ndarray:
    """Pooled final-conv activity (B, width) of one student, computed in
    ``x``'s dtype."""
    if spec["kind"] == "wrn":
        h = _conv_apply(p["conv0"], x)
        n = (spec["depth"] - 4) // 6
        for i, (cout, stride) in enumerate(wrn_stages(spec, width)):
            blk = p[f"g{i // n}b{i % n}"]
            a = jax.nn.relu(_bn_apply(blk["bn1"], h))
            if "shortcut" in blk:
                sc = _conv_apply(blk["shortcut"], a, stride)
            elif stride != 1:
                sc = h[:, ::stride, ::stride, :]
            else:
                sc = h
            t = _conv_apply(blk["conv1"], a, stride)
            t = _conv_apply(blk["conv2"],
                            jax.nn.relu(_bn_apply(blk["bn2"], t)))
            h = t + sc
        h = jax.nn.relu(_bn_apply(p["bn_out"], h))
    else:
        h = jnp.clip(_bn_apply(p["bn0"], _conv_apply(p["conv0"], x)), 0, 6)
        for i, (exp, cout, stride) in enumerate(mbv2_stages(spec)):
            blk = p[f"b{i}"]
            t = h if blk["expand"] is None else _conv_apply(blk["expand"], h)
            t = jnp.clip(_bn_apply(blk["bn0"], t), 0, 6)
            t = _conv_apply(blk["dw"], t, stride, groups=t.shape[-1])
            t = jnp.clip(_bn_apply(blk["bn1"], t), 0, 6)
            t = _bn_apply(blk["bn2"], _conv_apply(blk["project"], t))
            h = t + h if (stride == 1 and h.shape[-1] == cout) else t
        h = jnp.clip(_bn_apply(p["bn_last"],
                               _conv_apply(p["conv_last"], h)), 0, 6)
    return jnp.mean(h, axis=(1, 2))


@functools.partial(jax.jit, static_argnames=("spec_items", "width", "dtype"))
def _features_jit(p, x, *, spec_items, width, dtype):
    spec = {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in spec_items}
    cast = jax.tree.map(lambda a: a.astype(dtype), p)
    return features(cast, spec, width, x.astype(dtype)).astype(jnp.float32)


def _freeze(spec: Dict) -> tuple:
    def fz(v):
        if isinstance(v, list):
            return tuple(fz(u) for u in v)
        return v
    return tuple(sorted((k, fz(v)) for k, v in spec.items()))


def slot_features(weights: Dict, archs: Dict, slots: Sequence[tuple],
                  x: np.ndarray, *, dtype=jnp.float32,
                  precision: str = "highest", block: int = 256
                  ) -> np.ndarray:
    """(K, B, max width) features of every slot for rows ``x``, zero-padded
    to the widest slot; computed in blocks of ``block`` rows."""
    K, B = len(slots), x.shape[0]
    Dk = max(w for _, w in slots)
    out = np.zeros((K, B, Dk), np.float32)
    with jax.default_matmul_precision(precision):
        for k, (a, w) in enumerate(slots):
            spec = _freeze(arch_spec(archs, a))
            for s in range(0, B, block):
                xb = x[s:s + block]
                pad = block - xb.shape[0]        # one compiled shape
                if pad:
                    xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                                      xb.dtype)])
                f = _features_jit(weights["students"][k], jnp.asarray(xb),
                                  spec_items=spec, width=w, dtype=dtype)
                out[k, s:s + block, :w] = np.asarray(f)[:xb.shape[0] - pad]
    return out


def merge(weights: Dict, slots: Sequence[tuple], feats: np.ndarray,
          row_mask: np.ndarray, *, dtype=jnp.float32,
          precision: str = "highest") -> np.ndarray:
    """Quorum merge of ``feats`` (K, B, Dk) under the per-row arrived mask
    ``row_mask`` (B, K): (B, C) logits."""
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
