"""Build a configuration's deployment plan once, with the program's planner.

The plan (groups, knowledge partitions, students and their widths) is part
of a configuration: it is written into ``bench/configs/<name>.json`` and the
activation graph into ``bench/configs/<name>.graph.npy``, so that every run
serves the same deployment and no run pays for the teacher.

The recipe is the paper's offline phase up to the planner, on an untrained
teacher: a WRN teacher initialised from ``plan_seed``, its final-conv
activity over 256 synthetic images, the activation graph, the zoo profiled
at a nominal width, and ``planner.tune_d_th_ir`` over ``make_fleet``.

Run from the repository root, once per configuration (on the chip for the
full-size teachers):

    python3 bench/make_plan.py bench/configs/rocoin-c10.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import activation_graph as AG  # noqa: E402
from repro.core import planner as PL  # noqa: E402
from repro.core.pipeline import profile_student  # noqa: E402
from repro.core.simulator import make_fleet  # noqa: E402
from repro.data.images import ImageTaskConfig, SyntheticImages  # noqa: E402
from repro.models import cnn  # noqa: E402


def build_plan(cfg: dict) -> tuple:
    """(plan dict, activation graph) for the configuration ``cfg``."""
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", type=pathlib.Path)
    args = ap.parse_args(argv)
    cfg = json.loads(args.config.read_text())
    plan, A = build_plan(cfg)
    cfg["plan"] = plan
    cfg["plan_built_on"] = jax.devices()[0].device_kind
    np.save(args.config.with_suffix(".graph.npy"), A)
    args.config.write_text(json.dumps(cfg, indent=1) + "\n")
    print(json.dumps({"config": cfg["name"], "K": len(plan["slots"]),
                      "slots": [(s["arch"], s["width"], len(s["members"]))
                                for s in plan["slots"]],
                      "feasible": plan["feasible"], "d_th": plan["d_th"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
