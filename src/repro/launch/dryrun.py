import os
# _DRYRUN_HOST_DEVICES shrinks the forced host-device count (e.g. 8 for
# the --tiny cells that benchmarks/roofline.py reports)
os.environ["XLA_FLAGS"] = (
    os.environ.get("_DRYRUN_EXTRA_XLA", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("_DRYRUN_HOST_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this prints/records:
  - compiled.memory_analysis()  (proves the program fits per-chip HBM)
  - compiled.cost_analysis()    (per-chip FLOPs / bytes for the roofline)
  - collective schedule + modeled wire bytes (parsed from optimized HLO)

Results append to benchmarks/results/dryrun.json so the sweep is resumable.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--single-pod]
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.base import (SHAPES, ShapeConfig, all_archs,
                                applicable_shapes, get_config)
from repro.launch import roofline as RL
from repro.launch import steps as ST
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.parallel.sharding import axis_rules

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results"

# --tiny mode: same shape *kinds* at smoke scale, compiled on a host mesh —
# gives benchmarks/roofline.py a roofline artifact without a 512-device
# multi-pod sweep
TINY_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 256, 8, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 512, 4, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 512, 8, "decode"),
    "long_500k": ShapeConfig("long_500k", 2048, 1, "decode"),
}


def active_param_fraction_tree(cfg):
    """Per-leaf multiplier for MODEL_FLOPS: MoE expert weights count top_k/E."""
    import jax.tree_util as jtu
    from repro.parallel.specs import _path_str

    shapes = jax.eval_shape(lambda: __import__("repro.models.api", fromlist=["api"]).init(
        jax.random.key(0), cfg))
    total, active = 0, 0
    for path, leaf in jtu.tree_leaves_with_path(shapes):
        p = _path_str(path)
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if "embed/embedding" in p:
            continue  # gather, not matmul
        if cfg.n_experts and ("ffn/wi" in p or "ffn/wo" in p) and len(leaf.shape) == 3:
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    return total, active


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, verbose=True,
             tiny: bool = False):
    cfg = get_config(arch)
    if tiny:
        from repro.configs.archs import tiny_version
        cfg = tiny_version(cfg)
        shape = TINY_SHAPES[shape_name]
        n = len(jax.devices())
        mesh = make_host_mesh(2 if n % 2 == 0 and n > 1 else 1)
    else:
        shape = SHAPES[shape_name]
        mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ST.make_rules(cfg, shape, mesh)
    t0 = time.time()
    with axis_rules(rules, mesh), mesh:
        fn = ST.step_fn_for(cfg, shape)
        args = ST.input_specs(cfg, shape, mesh)
        if shape.kind == "train":
            jfn = jax.jit(fn, donate_argnums=(0,))
        elif shape.kind == "decode":
            jfn = jax.jit(fn, donate_argnums=(1,))
        else:
            jfn = jax.jit(fn)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    n_dev = mesh.devices.size
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}
    roof = RL.analyze(compiled, n_dev)

    total_p, active_p = active_param_fraction_tree(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = RL.model_flops(total_p, int(active_p), tokens,
                        "train" if shape.kind == "train" else "fwd")
    mf_per_chip = mf / n_dev
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": (f"host{n_dev}" if tiny
                 else "2x16x16" if multi_pod else "16x16"),
        "tiny": tiny,
        "n_devices": n_dev, "kind": shape.kind,
        "params": total_p, "active_params": active_p,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_d,
        "roofline": roof.to_dict(),
        "model_flops_per_chip": mf_per_chip,
        "useful_ratio": (mf_per_chip / roof.flops) if roof.flops else None,
        "ok": True,
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {rec['mesh']}] "
              f"compile={t_compile:.0f}s flops/chip={roof.flops:.3e} "
              f"bytes/chip={roof.bytes_accessed:.3e} coll/chip={roof.collective_bytes:.3e}")
        print(f"  memory_analysis: {mem_d}")
        print(f"  terms: compute={roof.compute_s*1e3:.2f}ms memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms dominant={roof.dominant} "
              f"useful_ratio={rec['useful_ratio'] and round(rec['useful_ratio'],3)}")
        print(f"  collectives: {roof.collective_counts}")
    return rec


def _load(path):
    if path.exists():
        return json.loads(path.read_text())
    return {}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke scale: tiny configs/shapes on a host mesh")
    ap.add_argument("--out", type=str, default=str(RESULTS / "dryrun.json"))
    args = ap.parse_args()

    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = _load(out_path)

    meshes = []
    if args.single_pod or not args.multi_pod:
        meshes.append(False)
    if args.multi_pod or (not args.single_pod and args.all):
        meshes.append(True)

    cells = []
    if args.all:
        for name, cfg in all_archs().items():
            for sh in applicable_shapes(cfg):
                cells.append((name, sh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    failures = []
    for arch, sh in cells:
        for mp in meshes:
            mesh_tag = "tiny" if args.tiny else ("multi" if mp else "single")
            key = f"{arch}|{sh}|{mesh_tag}"
            if key in results and results[key].get("ok") and not args.force:
                print(f"skip cached {key}")
                continue
            try:
                rec = run_cell(arch, sh, mp, tiny=args.tiny)
            except Exception as e:
                traceback.print_exc()
                rec = {"arch": arch, "shape": sh,
                       "mesh": "tiny" if args.tiny
                       else "2x16x16" if mp else "16x16",
                       "ok": False, "error": f"{type(e).__name__}: {e}"}
                failures.append(key)
            results[key] = rec
            out_path.write_text(json.dumps(results, indent=1))
    print(f"\n{len(cells)*len(meshes)} cells, {len(failures)} failures")
    for f in failures:
        print("  FAIL", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
