"""Build a configuration's deployment plan once, with the program's planner.

The plan (groups, knowledge partitions, students and their widths) is part
of a configuration: it is written into ``bench/configs/<name>.json`` and the
activation graph into ``bench/configs/<name>.graph.npy``, so that every run
serves the same deployment and no run pays for the teacher.

The recipe is the configuration's kind's (``build_plan`` of
``bench/kinds/<kind>.py``): for ``cnn``, the paper's offline phase up to
the planner, on an untrained teacher.

Run from the repository root, once per configuration (on the chip for the
full-size teachers):

    python3 bench/make_plan.py bench/configs/rocoin-c10.json
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import deploy  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config", type=pathlib.Path)
    args = ap.parse_args(argv)
    cfg = json.loads(args.config.read_text())
    plan, A = deploy.kind_of(cfg).build_plan(cfg)
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
