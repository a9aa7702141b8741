"""A configuration file made into the program's objects.

``load_config`` reads ``bench/configs/<name>.json`` (and its activation
graph); ``build`` hands it to its kind (``bench/kinds/<kind>.py``), which
makes the weights from the seed on the device in one jitted call and
serves them through the program's normal path: ``PlanIR`` -> ``Ensemble``
-> ``server_from_ensemble`` (``serve_ensemble``). The same weight arrays
stay with the benchmark for the reference.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, List, Sequence

import jax
import numpy as np

from bench import kinds

BENCH = pathlib.Path(__file__).resolve().parent


def key_for(seed: int, salt: int = 0) -> jax.Array:
    """A JAX key from any whole ``seed`` (large or negative ones too)."""
    words = np.random.SeedSequence([int(seed) % (1 << 64), salt]
                                   ).generate_state(1)
    return jax.random.key(int(words[0]))


def load_config(name_or_path) -> Dict:
    """The configuration by name (``bench/configs/<name>.json``) or path,
    with its activation graph as ``graph``."""
    path = pathlib.Path(name_or_path)
    if path.suffix != ".json":
        path = BENCH / "configs" / f"{name_or_path}.json"
    cfg = json.loads(path.read_text())
    if "plan" not in cfg:
        raise ValueError(f"{path} has no plan; build it with make_plan.py")
    cfg["graph"] = np.load(path.with_suffix(".graph.npy"))
    return cfg


def kind_of(cfg: Dict):
    """The module of the configuration's kind: its ``"kind"``, else
    ``cnn``."""
    return kinds.load(cfg.get("kind", "cnn"))


def slot_shapes(cfg: Dict) -> tuple:
    """((arch, width), ...) of the plan's slots, slot order."""
    return tuple((s["arch"], int(s["width"])) for s in cfg["plan"]["slots"])


@dataclasses.dataclass
class Deployment:
    """The program's server over one configuration, and the benchmark's own
    copy of what it serves."""
    cfg: Dict
    kind: Any                    # the module bench/kinds/<kind>.py
    slots: tuple                 # ((arch, width), ...)
    weights: Any                 # the kind's reference layout, device arrays
    server: Any                  # repro.runtime.serving.QuorumServer
    ir: Any                      # repro.core.plan_ir.PlanIR as deployed


def plan_ir(cfg: Dict):
    """The configuration's frozen plan as the program's ``PlanIR``."""
    from repro.core.assignment import StudentArch
    from repro.core.grouping import Device
    from repro.core.plan_ir import (PlanIR, device_matrix, eq1a_latency,
                                    student_matrix)
    plan = cfg["plan"]
    devices = [Device(**d) for d in plan["devices"]]
    students = [StudentArch(**s) for s in plan["students"]]
    names, dcaps = device_matrix(devices)
    snames, scaps = student_matrix(students)
    col = {n: i for i, n in enumerate(names)}
    K, M = len(plan["slots"]), cfg["graph"].shape[0]
    member = np.zeros((K, len(names)), bool)
    partition = np.zeros((K, M), bool)
    for k, s in enumerate(plan["slots"]):
        member[k, [col[n] for n in s["members"]]] = True
        partition[k, s["filters"]] = True
    return PlanIR(names, dcaps, snames, scaps, member, partition,
                  np.array([s["student"] for s in plan["slots"]]),
                  np.array([s["group"] for s in plan["slots"]]),
                  eq1a_latency(scaps, dcaps), cfg["graph"].astype(np.float64),
                  plan["d_th"], plan["p_th"]).validate()


def same_layout(ours, theirs, what: str) -> None:
    """Refuse weights whose pytree of shapes and dtypes is not the
    program's."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise ValueError(f"{what}: the program's parameter layout differs "
                         f"from the configuration's architecture")


def serve_ensemble(cfg: Dict, kind, weights, students: Sequence, fc: Dict,
                   seed: int) -> Deployment:
    """``students`` ((program config, params, forward) per slot) and the
    merge head ``fc`` served by ``server_from_ensemble`` over the plan."""
    from repro.core.pipeline import Ensemble
    from repro.runtime.serving import server_from_ensemble
    slots = slot_shapes(cfg)
    ir = plan_ir(cfg)
    ens = Ensemble(plan=ir.to_plan(), students=list(students), fc=fc,
                   part_dims=[w for _, w in slots], teacher_acc=float("nan"),
                   ir=ir)
    server = server_from_ensemble(ens, seed=int(seed) % (1 << 32))
    return Deployment(cfg, kind, slots, weights, server, ir)


def build(cfg: Dict, seed: int) -> Deployment:
    """The configuration's kind builds its deployment from ``seed``."""
    return kind_of(cfg).build(cfg, seed)


def group_members(cfg: Dict, group: int) -> List[str]:
    """Device names of the plan's device group ``group``."""
    for s in cfg["plan"]["slots"]:
        if s["group"] == group:
            return list(s["members"])
    raise KeyError(f"the plan has no group {group}")


def describe(dep: Deployment) -> str:
    srv = dep.server
    path = "fused megastep" if srv.fastpath_active else "per-slot loop"
    return (f"{pathlib.Path(dep.kind.__file__).stem} K={len(dep.slots)} path={path} slots="
            + ", ".join(f"{a}/{w}x{len(s['members'])}" for (a, w), s in
                        zip(dep.slots, dep.cfg["plan"]["slots"])))
