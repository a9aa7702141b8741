"""The kind ``cnn``: RoCoIn's image classifiers (the paper's deployments).

Students are WRNs or MobileNetV2s sized to their knowledge portions, and a
portion is a student's pooled final-conv activity; a request is one or more
images of ``image_shape``, rows of a pool made from the seed. The
configuration describes the architectures (``archs``) and the plan.

The reference knows the architectures from the configuration, makes the
weights from the seed, and computes every slot's portion with plain
``jax.numpy``. The parameter layout is the usual one for these networks
(per block: ``bn1``/``conv1``/``bn2``/``conv2``/``shortcut`` for a
pre-activation WRN block; ``expand``/``bn0``/``dw``/``bn1``/``project``/
``bn2`` for an inverted residual), so the harness can hand the same arrays
to the program, whose forwards (``repro.models.cnn``) it serves them with.

Conventions that the networks' papers leave open are fixed here as the
configuration states them: "SAME" padding (a strided 3x3 pads one row and
column after the image), a strided identity shortcut subsamples, and
batch norm runs in inference mode with eps 1e-5.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import deploy, kinds
from bench import reference as R
from bench.counts import quorum_aggregate as QA
from bench.counts import student_forward as SF

BN_EPS = 1e-5
POOL_ROWS = 4096          # distinct images cycled through by the requests

# Limits of the numbers compared for ``correct`` (PERF.md gives the
# readings each was set from).
LIMITS = {"rel_gap_p90": 4e-3, "max_rel_err": 5e-2}


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
    return R.drawn(key, lambda d: _student(d, spec, width, n_classes, in_ch))


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
    return R.drawn(key, build)


def make_weights(cfg: Dict, seed: int) -> Dict:
    """Every slot's student and the FC head, from ``seed``, on the device,
    in one jitted call (float32, as served)."""
    slots = deploy.slot_shapes(cfg)
    fn = jax.jit(lambda k: init_ensemble(k, cfg["archs"], slots,
                                         cfg["n_classes"]))
    return jax.block_until_ready(fn(deploy.key_for(seed)))


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


def reference(dep, xs: Sequence[np.ndarray], masks: Sequence[np.ndarray], *,
              control: bool = False) -> List[np.ndarray]:
    """(rows, C) logits of each request's images ``xs[i]`` merged under its
    arrived mask ``masks[i]``, in float32 at the matmul precisions the
    configuration states (``convolutions`` for the students, ``merge`` for
    the FC merge); with ``control``, in its ``control_dtype`` at the
    default precision."""
    prec = dep.cfg["precision"]
    x = np.concatenate(xs)
    row_mask = np.repeat(np.asarray(masks), [len(a) for a in xs], axis=0)
    if control:
        dt = {"bfloat16": jnp.bfloat16}[prec["control_dtype"]]
        conv_p = merge_p = "default"
    else:
        dt, conv_p, merge_p = jnp.float32, prec["convolutions"], prec["merge"]
    feats = slot_features(dep.weights, dep.cfg["archs"], dep.slots, x,
                          dtype=dt, precision=conv_p)
    out = R.merge(dep.weights, dep.slots, feats, row_mask, dtype=dt,
                  precision=merge_p)
    return np.split(out, np.cumsum([len(a) for a in xs])[:-1])


# ---------------------------------------------------------------------------
# the program's deployment, inputs and counts
# ---------------------------------------------------------------------------

def _program_student(arch: str, width: int, n_classes: int):
    """(config, forward) of the program's student ``arch`` at ``width``."""
    from repro.models import cnn
    if arch.startswith("wrn"):
        _, d, w = arch.split("-")
        return (cnn.WRNConfig(arch, int(d), int(w), n_classes,
                              final_channels=width), cnn.wrn_forward)
    if arch == "mobilenetv2":
        return cnn.MBV2Config(arch, n_classes, final_channels=width), \
            cnn.mbv2_forward
    raise KeyError(arch)


def build(cfg: Dict, seed: int) -> deploy.Deployment:
    """Weights from ``seed`` served by ``server_from_ensemble``."""
    from repro.models import cnn
    slots = deploy.slot_shapes(cfg)
    weights = make_weights(cfg, seed)
    n_classes = cfg["n_classes"]
    students: List = []
    for k, (arch, width) in enumerate(slots):
        pcfg, fwd = _program_student(arch, width, n_classes)
        shapes = jax.eval_shape(
            lambda: cnn.make_student(jax.random.key(0), arch, n_classes,
                                     width)[1])
        deploy.same_layout(weights["students"][k], shapes,
                           f"slot {k} ({arch})")
        students.append((pcfg, weights["students"][k], fwd))
    return deploy.serve_ensemble(cfg, sys.modules[__name__], weights,
                                 students, weights["fc"], seed)


def image_pool(cfg: Dict, seed: int, rows: int = POOL_ROWS) -> np.ndarray:
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    return rng.standard_normal((rows,) + tuple(cfg["image_shape"]),
                               np.float32)


def inputs(cfg: Dict, seed: int, sizes: Sequence[int]) -> kinds.PoolInputs:
    """Each request's own images, rows of the seed's image pool."""
    return kinds.PoolInputs(image_pool(cfg, seed), sizes)


def slot_cost(cfg: Dict, arch: str, width: int, rows: int) -> tuple:
    """(operations, least bytes) of one student forward over ``rows``
    images (``bench/counts/student_forward.py``)."""
    spec, shape = arch_spec(cfg["archs"], arch), tuple(cfg["image_shape"])
    return (SF.flops(spec, width, shape, rows),
            SF.bytes_moved(spec, width, shape, rows))


def merge_cost(cfg: Dict, arrived: int, rows: int, dk: int) -> tuple:
    """(operations, least bytes) of one float32 quorum merge over the
    configuration's classes."""
    C = cfg["n_classes"]
    return (QA.flops(arrived, rows, dk, C),
            QA.bytes_moved(arrived, rows, dk, C))


# ---------------------------------------------------------------------------
# the plan (bench/make_plan.py)
# ---------------------------------------------------------------------------

def build_plan(cfg: Dict) -> tuple:
    """(plan dict, activation graph) for the configuration ``cfg``: a WRN
    teacher initialised from ``plan_seed``, its final-conv activity over
    256 synthetic images, the activation graph, the zoo profiled at a
    nominal width, and ``planner.tune_d_th_ir`` over ``make_fleet``."""
    from repro.core import activation_graph as AG
    from repro.core import planner as PL
    from repro.core.pipeline import profile_student
    from repro.core.simulator import make_fleet
    from repro.data.images import ImageTaskConfig, SyntheticImages
    from repro.models import cnn
    t = cfg["teacher"]
    n_classes = cfg["n_classes"]
    tcfg = cnn.WRNConfig(t["arch"], t["depth"], t["widen"], n_classes)
    tparams = cnn.wrn_init(jax.random.key(cfg["plan_seed"]), tcfg)
    data = SyntheticImages(ImageTaskConfig(n_classes=n_classes,
                                           seed=cfg["plan_seed"]))
    xs, _ = data.batch(256, 77_000)
    _, feats, _ = jax.jit(lambda p, x: cnn.wrn_forward(p, tcfg, x))(
        tparams, jnp.asarray(xs))
    A = np.asarray(AG.activation_graph(AG.average_activity(feats)),
                   np.float32)
    M = A.shape[0]
    if M != t["final_filters"]:
        raise ValueError(f"teacher has {M} final filters, the configuration "
                         f"says {t['final_filters']}")
    f = cfg["fleet"]
    devices = [dataclasses.replace(d, name=f"d{i}") for i, d in enumerate(
        make_fleet(f["n"], seed=f["seed"], flops_range=tuple(f["flops_range"]),
                   rate_range=tuple(f["rate_range"]),
                   mem_range=tuple(f["mem_range"]),
                   success_prob=f["success_prob"]))]
    nominal_width = max(M // max(len(devices) // 2, 1), 8)
    zoo = [profile_student(n, n_classes, nominal_width, xs[:1])
           for n in cfg["zoo"]]
    ir = PL.tune_d_th_ir(devices, A.astype(np.float64), zoo, p_th=cfg["p_th"])
    if ir is None or not (ir.student_of >= 0).all():
        raise ValueError("the planner placed no student on some group; widen "
                         "the fleet's mem_range")
    slots = []
    for k in range(ir.K):
        name = ir.student_names[int(ir.student_of[k])]
        filters = np.flatnonzero(ir.partition[k])
        slots.append({"arch": name.rsplit("-f", 1)[0],
                      "width": int(len(filters)),
                      "student": int(ir.student_of[k]),
                      "group": int(ir.group_idx[k]),
                      "members": [ir.device_names[n]
                                  for n in np.flatnonzero(ir.member[k])],
                      "filters": filters.tolist()})
    plan = {
        "d_th": ir.d_th, "p_th": ir.p_th, "feasible": bool(ir.feasible),
        "nominal_width": nominal_width,
        "devices": [dataclasses.asdict(d) for d in devices],
        "students": [dataclasses.asdict(s) for s in zoo],
        "slots": slots,
    }
    return plan, A
