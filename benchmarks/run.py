"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run               # everything
  PYTHONPATH=src python -m benchmarks.run --only fig7   # one table/figure
  BENCH_BUDGET=full ... run                             # full step budgets
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

MODULES = [
    "benchmarks.roofline",             # reads the dry-run artifact
    "benchmarks.bench_roofline",       # kernel efficiency vs measured roofline
    "benchmarks.sim_speed",            # Monte-Carlo engine: loop vs vectorized
    "benchmarks.plan_scale",           # PlanIR planner scale + controller
    "benchmarks.bench_fastpath",       # fused fast path: serial vs fused vs int8
    "benchmarks.bench_serving",        # continuous-batching engine + chaos
    "benchmarks.bench_fleet",          # multi-tenant fleet: shared spare pool
    "benchmarks.bench_obs",            # tracing/metrics overhead + validity
    "benchmarks.bench_coding",         # replicate-K vs coded-(n,k) redundancy
    "benchmarks.bench_coded_compute",  # first-k compute shards vs stragglers
    "benchmarks.bench_failout",        # failout vs failure-blind distillation
    "benchmarks.fig4_redundancy",      # planner only
    "benchmarks.fig7_heterogeneity",   # planner + simulator
    "benchmarks.fig3_latency",         # simulator + one trained ensemble
    "benchmarks.table2_cifar10",       # trains 4 planner variants
    "benchmarks.fig2_training",        # reuses table2 ensembles
    "benchmarks.fig5_failures",        # reuses table2 ensembles
    "benchmarks.fig6_failures_unknown",
    "benchmarks.table3_cifar100",
    "benchmarks.table5_detection_proxy",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            mod.main()
            print(f"{mod_name}.total,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{mod_name}.total,{(time.time()-t0)*1e6:.0f},FAILED:{type(e).__name__}")
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
