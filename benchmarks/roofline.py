"""Roofline report: the three roofline terms per (arch × shape × mesh) —
the §Roofline table of EXPERIMENTS.md.

Reads the dry-run artifact ``benchmarks/results/dryrun.json``. The dry-run
forces its host-device count through ``XLA_FLAGS`` when it is imported, so
it runs in a process of its own, before this report and not from it::

  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \\
      --shape train_4k --tiny
  PYTHONPATH=src python -m benchmarks.run --only benchmarks.roofline

A missing artifact or a failed cell in it is an error, so
``benchmarks/run.py`` exits non-zero instead of reporting the row.
"""
from __future__ import annotations

import json
import pathlib

from benchmarks.common import emit

RESULTS = pathlib.Path(__file__).resolve().parent / "results" / "dryrun.json"


def main() -> None:
    if not RESULTS.exists():
        raise FileNotFoundError(
            f"{RESULTS} is missing: run `python -m repro.launch.dryrun` "
            f"(see this module's docstring) first")
    data = json.loads(RESULTS.read_text())
    failed = sorted(key for key, rec in data.items() if not rec.get("ok"))
    if failed:
        raise RuntimeError(f"dry-run cells failed: {failed}")
    for key, rec in sorted(data.items()):
        r = rec["roofline"]
        bound = max(r["compute_s"], r["memory_s"], r["collective_s"])
        frac = r["compute_s"] / bound if bound > 0 else 0.0
        emit(f"roofline/{key}", rec.get("compile_s", 0) * 1e6,
             f"compute_ms={r['compute_s']*1e3:.2f};"
             f"memory_ms={r['memory_s']*1e3:.2f};"
             f"collective_ms={r['collective_s']*1e3:.2f};"
             f"dominant={r['dominant']};roofline_frac={frac:.3f};"
             f"useful_ratio={rec.get('useful_ratio') and round(rec['useful_ratio'], 3)}")


if __name__ == "__main__":
    main()
