"""The paper's teacher/student CNN zoo: WideResNet-depth-width and
MobileNetV2 (CIFAR variant), pure-functional JAX with explicit BN state.

Teachers: WRN-16-4 (CIFAR-10), WRN-28-10 (CIFAR-100).
Students: WRN-22-1 / WRN-16-1 / MobileNetV2 (CIFAR-10);
          WRN-16-3 / WRN-16-2 / WRN-22-1 (CIFAR-100).

Students expose a configurable number of final-conv channels so each student
can be sized to its knowledge partition (NoNN-style): the final features are
the student's "portion" of the teacher's final conv layer.

forward(...) returns (logits, final_features, new_bn_state); final_features
are the spatially-pooled final-conv activations used for the AT loss and for
RoCoIn's quorum aggregation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# WideResNet
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WRNConfig:
    name: str
    depth: int            # 6n+4
    widen: int
    n_classes: int
    final_channels: Optional[int] = None  # override last-group width (students)
    in_channels: int = 3

    @property
    def n_blocks(self) -> int:
        assert (self.depth - 4) % 6 == 0, self.depth
        return (self.depth - 4) // 6

    @property
    def widths(self) -> Tuple[int, int, int]:
        w = self.widen
        out = [16 * w, 32 * w, 64 * w]
        if self.final_channels:
            out[2] = self.final_channels
        return tuple(out)


def _bn_relu_init(ch):
    return L.batchnorm_init(ch)


def _basic_init(key, cin, cout):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "bn1": _bn_relu_init(cin),
        "conv1": L.conv2d_init(k1, cin, cout, 3),
        "bn2": _bn_relu_init(cout),
        "conv2": L.conv2d_init(k2, cout, cout, 3),
    }
    if cin != cout:
        p["shortcut"] = L.conv2d_init(k3, cin, cout, 1)
    return p


def _basic_apply(p, x, *, stride, train):
    h, bn1 = L.batchnorm_apply(p["bn1"], x, train=train)
    h = jax.nn.relu(h)
    sc = x
    if "shortcut" in p:
        sc = L.conv2d_apply(p["shortcut"], h, stride=stride)
    elif stride != 1:
        sc = x[:, ::stride, ::stride, :]
    h = L.conv2d_apply(p["conv1"], h, stride=stride)
    h2, bn2 = L.batchnorm_apply(p["bn2"], h, train=train)
    h = L.conv2d_apply(p["conv2"], jax.nn.relu(h2))
    newp = {**p, "bn1": bn1, "bn2": bn2}
    return h + sc, newp


def wrn_init(key, cfg: WRNConfig) -> Params:
    keys = jax.random.split(key, 3 * cfg.n_blocks + 3)
    ki = iter(range(len(keys)))
    w1, w2, w3 = cfg.widths
    p: Params = {"conv0": L.conv2d_init(keys[next(ki)], cfg.in_channels, 16, 3)}
    cin = 16
    for gi, (w, _) in enumerate(zip((w1, w2, w3), range(3))):
        for bi in range(cfg.n_blocks):
            p[f"g{gi}b{bi}"] = _basic_init(keys[next(ki)], cin, w)
            cin = w
    p["bn_out"] = _bn_relu_init(cin)
    p["fc"] = L.dense_init(keys[next(ki)], cin, cfg.n_classes, use_bias=True)
    return p


def wrn_forward(p: Params, cfg: WRNConfig, x: jnp.ndarray, *, train: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Params]:
    """x: (B,32,32,3) → (logits, final_feats (B, C_final), new_params)."""
    newp = dict(p)
    h = L.conv2d_apply(p["conv0"], x)
    for gi in range(3):
        stride = 1 if gi == 0 else 2
        for bi in range(cfg.n_blocks):
            h, np_ = _basic_apply(p[f"g{gi}b{bi}"], h,
                                  stride=(stride if bi == 0 else 1), train=train)
            newp[f"g{gi}b{bi}"] = np_
    h, bno = L.batchnorm_apply(p["bn_out"], h, train=train)
    newp["bn_out"] = bno
    h = jax.nn.relu(h)               # (B,8,8,C) final conv activations
    feats = jnp.mean(h, axis=(1, 2))  # average activity per filter
    logits = L.dense_apply(p["fc"], feats)
    return logits, feats, newp


# ---------------------------------------------------------------------------
# MobileNetV2 (CIFAR)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MBV2Config:
    name: str
    n_classes: int
    width_mult: float = 1.0
    final_channels: int = 320
    in_channels: int = 3


_MBV2_BLOCKS = [  # (expansion, out_ch, n, stride) — CIFAR variant
    (1, 16, 1, 1), (6, 24, 2, 1), (6, 32, 3, 2), (6, 64, 2, 2), (6, 96, 1, 1),
]


def _inv_res_init(key, cin, cout, exp):
    k1, k2, k3 = jax.random.split(key, 3)
    mid = cin * exp
    return {
        "expand": L.conv2d_init(k1, cin, mid, 1) if exp != 1 else None,
        "bn0": L.batchnorm_init(mid),
        "dw": L.conv2d_init(k2, mid, mid, 3, groups=mid),
        "bn1": L.batchnorm_init(mid),
        "project": L.conv2d_init(k3, mid, cout, 1),
        "bn2": L.batchnorm_init(cout),
    }


def _inv_res_apply(p, x, *, stride, train):
    h = x
    newp = dict(p)
    if p["expand"] is not None:
        h = L.conv2d_apply(p["expand"], h)
    h, newp["bn0"] = L.batchnorm_apply(p["bn0"], h, train=train)
    h = jax.nn.relu6(h)
    h = L.conv2d_apply(p["dw"], h, stride=stride, groups=h.shape[-1])
    h, newp["bn1"] = L.batchnorm_apply(p["bn1"], h, train=train)
    h = jnp.clip(h, 0, 6)
    h = L.conv2d_apply(p["project"], h)
    h, newp["bn2"] = L.batchnorm_apply(p["bn2"], h, train=train)
    if stride == 1 and x.shape[-1] == h.shape[-1]:
        h = h + x
    return h, newp


def mbv2_init(key, cfg: MBV2Config) -> Params:
    n_blocks = sum(n for _, _, n, _ in _MBV2_BLOCKS)
    keys = jax.random.split(key, n_blocks + 3)
    ki = iter(range(len(keys)))
    p: Params = {"conv0": L.conv2d_init(keys[next(ki)], cfg.in_channels, 32, 3),
                 "bn0": L.batchnorm_init(32)}
    cin = 32
    idx = 0
    for exp, cout, n, stride in _MBV2_BLOCKS:
        cout = int(cout * cfg.width_mult)
        for i in range(n):
            p[f"b{idx}"] = _inv_res_init(keys[next(ki)], cin, cout, exp)
            cin = cout
            idx += 1
    p["conv_last"] = L.conv2d_init(keys[next(ki)], cin, cfg.final_channels, 1)
    p["bn_last"] = L.batchnorm_init(cfg.final_channels)
    p["fc"] = L.dense_init(keys[next(ki)], cfg.final_channels, cfg.n_classes,
                           use_bias=True)
    return p


def mbv2_forward(p: Params, cfg: MBV2Config, x: jnp.ndarray, *,
                 train: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray, Params]:
    newp = dict(p)
    h = L.conv2d_apply(p["conv0"], x)
    h, newp["bn0"] = L.batchnorm_apply(p["bn0"], h, train=train)
    h = jnp.clip(h, 0, 6)
    idx = 0
    for exp, cout, n, stride in _MBV2_BLOCKS:
        for i in range(n):
            h, newp[f"b{idx}"] = _inv_res_apply(p[f"b{idx}"], h,
                                                stride=(stride if i == 0 else 1),
                                                train=train)
            idx += 1
    h = L.conv2d_apply(p["conv_last"], h)
    h, newp["bn_last"] = L.batchnorm_apply(p["bn_last"], h, train=train)
    h = jnp.clip(h, 0, 6)
    feats = jnp.mean(h, axis=(1, 2))
    logits = L.dense_apply(p["fc"], feats)
    return logits, feats, newp


# ---------------------------------------------------------------------------
# model zoo registry (paper §V-A) with FLOPs/param accounting
# ---------------------------------------------------------------------------

def count_params(p: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(p)
               if hasattr(x, "size"))


def make_student(key, name: str, n_classes: int, final_channels: int):
    """Instantiate a zoo student with its final conv sized to the partition."""
    if name.startswith("wrn"):
        _, d, w = name.split("-")
        cfg = WRNConfig(name, int(d), int(w), n_classes,
                        final_channels=final_channels)
        return cfg, wrn_init(key, cfg), wrn_forward
    if name == "mobilenetv2":
        cfg = MBV2Config(name, n_classes, final_channels=final_channels)
        return cfg, mbv2_init(key, cfg), mbv2_forward
    raise KeyError(name)


STUDENT_ZOO_C10 = ["wrn-22-1", "wrn-16-1", "mobilenetv2"]
STUDENT_ZOO_C100 = ["wrn-16-3", "wrn-16-2", "wrn-22-1"]
