"""A toy kind, ``toy``, that shows a second kind of configuration needs
only files of its own: this module and its configuration
(``bench/tests/data/toy.json``).

Students are two-layer MLPs over vectors of ``input_dim``: a portion is
``relu(tanh(x @ w1 + b1) @ w2 + b2)``, ``width`` wide. A request is one or
more rows of a pool of vectors made from the seed. The served forward is
this module's ``mlp_forward``, handed to the program's ``Ensemble`` ->
``server_from_ensemble`` as a CNN student's is; the reference computes the
same portions with ``einsum`` at the precision the configuration states,
and the control in its ``control_dtype``.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import deploy, kinds
from bench import reference as R
from bench.counts import quorum_aggregate as QA

POOL_ROWS = 1024
# set from the toy's own readings on the CPU over 12 seeds, 1.5 s windows
# of tiny-mix: rel_gap_p90 at most 3.0e-7 (program) against at least
# 6.2e-3 (bfloat16 control); max_rel_err at most 5.4e-7 (program) against
# at least 8.5e-3 (control) and 0.56 (planted fault)
LIMITS = {"rel_gap_p90": 1e-4, "max_rel_err": 1e-3}


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    """The served student's configuration."""
    name: str
    hidden: int
    width: int


def mlp_forward(params, cfg: MLPConfig, x):
    """(logits, portion, params), as the program's student forwards give."""
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return None, jax.nn.relu(h @ params["w2"] + params["b2"]), params


def _mlp(draw, d: int, hidden: int, width: int) -> Dict:
    return {"w1": draw.gauss((d, hidden), 1 / np.sqrt(d)),
            "b1": draw.gauss((hidden,), 0.1),
            "w2": draw.gauss((hidden, width), 1 / np.sqrt(hidden)),
            "b2": draw.gauss((width,), 0.1)}


def make_weights(cfg: Dict, seed: int) -> Dict:
    slots = deploy.slot_shapes(cfg)
    d, C = cfg["input_dim"], cfg["n_classes"]
    total = sum(w for _, w in slots)

    def build(draw):
        students = [_mlp(draw, d, cfg["archs"][a]["hidden"], w)
                    for a, w in slots]
        return {"students": students,
                "fc": {"kernel": draw.gauss((total, C), 1 / np.sqrt(total)),
                       "bias": draw.gauss((C,), 0.1)}}
    fn = jax.jit(lambda k: R.drawn(k, build))
    return jax.block_until_ready(fn(deploy.key_for(seed)))


def build(cfg: Dict, seed: int) -> deploy.Deployment:
    weights = make_weights(cfg, seed)
    students = [(MLPConfig(a, cfg["archs"][a]["hidden"], w),
                 weights["students"][k], mlp_forward)
                for k, (a, w) in enumerate(deploy.slot_shapes(cfg))]
    return deploy.serve_ensemble(cfg, sys.modules[__name__], weights,
                                 students, weights["fc"], seed)


def inputs(cfg: Dict, seed: int, sizes: Sequence[int]) -> kinds.PoolInputs:
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    pool = rng.standard_normal((POOL_ROWS, cfg["input_dim"]), np.float32)
    return kinds.PoolInputs(pool, sizes)


def reference(dep, xs: Sequence[np.ndarray], masks: Sequence[np.ndarray], *,
              control: bool = False) -> List[np.ndarray]:
    prec = dep.cfg["precision"]
    dt = jnp.dtype(prec["control_dtype"]) if control else jnp.float32
    p = "default" if control else prec["matmul"]
    x = jnp.asarray(np.concatenate(xs), dt)
    row_mask = np.repeat(np.asarray(masks), [len(a) for a in xs], axis=0)
    Dk = max(w for _, w in dep.slots)
    feats = np.zeros((len(dep.slots), x.shape[0], Dk), np.float32)
    with jax.default_matmul_precision(p):
        for k, (_, w) in enumerate(dep.slots):
            s = jax.tree.map(lambda a: a.astype(dt),
                             dep.weights["students"][k])
            h = jnp.tanh(jnp.einsum("bi,ih->bh", x, s["w1"]) + s["b1"])
            f = jnp.maximum(jnp.einsum("bh,hw->bw", h, s["w2"]) + s["b2"], 0)
            feats[k, :, :w] = np.asarray(f.astype(jnp.float32))
    out = R.merge(dep.weights, dep.slots, feats, row_mask, dtype=dt,
                  precision=p)
    return np.split(out, np.cumsum([len(a) for a in xs])[:-1])


def slot_cost(cfg: Dict, arch: str, width: int, rows: int) -> tuple:
    d, h = cfg["input_dim"], cfg["archs"][arch]["hidden"]
    flops = 2 * rows * (d * h + h * width)
    nbytes = 4 * (d * h + h + h * width + width + rows * (d + width))
    return float(flops), float(nbytes)


def merge_cost(cfg: Dict, arrived: int, rows: int, dk: int) -> tuple:
    C = cfg["n_classes"]
    return (QA.flops(arrived, rows, dk, C),
            QA.bytes_moved(arrived, rows, dk, C))
