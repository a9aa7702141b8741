"""What a metric reader gets: one run's records, its trace and its counts.

Each metric of ``BENCHMARK.json`` has a reader ``bench/metrics/<name>.py``
with ``read(run) -> float | None``; it returns None when the run holds
nothing for it to read (a share of a roofline is then left out, never 0).
Operations and bytes come from the configuration's kind (``slot_cost``,
``merge_cost``), counted from shapes alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, Optional

import numpy as np

from bench import trace_reduce
from bench.window import Window

BENCH = pathlib.Path(__file__).resolve().parent


def percentile(xs, q: float) -> float:
    """Linear-interpolation percentile (numpy's convention, as the
    program's ``obs/stats.py``); an unanswered request is +inf, and a
    percentile that reaches one is inf."""
    xs = np.sort(np.asarray(xs, np.float64))
    if xs.size == 0:
        return float("inf")
    pos = (xs.size - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(xs[hi]):
        return float("inf")
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def peaks(device_kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


@dataclasses.dataclass
class Run:
    kind: Any                  # the configuration's bench/kinds/<kind>.py
    cfg: Dict
    slots: tuple
    window: Window
    setup_s: float
    device_kind: str
    trace: Optional[trace_reduce.Trace] = None

    @property
    def seconds(self) -> float:
        return self.window.seconds

    @property
    def peaks(self) -> Dict:
        return peaks(self.device_kind)

    def in_window(self, t) -> np.ndarray:
        return np.asarray(t) <= self.seconds

    def traced_batches(self):
        """Batches dispatched while the profiler ran."""
        span = self.window.trace_span
        if span is None:
            return []
        return [b for b in self.window.batches
                if span[0] <= b.t_dispatch < span[1]]

    def least_s(self, flops: float, nbytes: float) -> float:
        """The least time the chip takes: the larger of operations over
        peak FLOP/s and bytes over peak bytes/s."""
        pk = self.peaks
        return max(flops / pk["bf16_flops_per_s"],
                   nbytes / pk["hbm_bytes_per_s"])

    def student_counts(self, batches) -> tuple:
        """(flops, least seconds) of the student forwards ``batches`` ran,
        padded rows included (the device computes them)."""
        fl = least = 0.0
        for b in batches:
            if b.computed is None:
                continue
            rows = b.rows + b.padded_rows
            for k in np.flatnonzero(b.computed):
                if k >= len(self.slots) or k >= len(b.slot_widths):
                    continue
                f, by = self.kind.slot_cost(self.cfg, self.slots[k][0],
                                            b.slot_widths[k], rows)
                fl += f
                least += self.least_s(f, by)
        return fl, least

    def merge_counts(self, batches) -> tuple:
        """(flops, least seconds, bound) of the quorum merges ``batches``
        ran."""
        pk = self.peaks
        fl = least = 0.0
        by_c = by_m = 0.0
        for b in batches:
            if b.computed is None or not b.slot_widths:
                continue
            f, by = self.kind.merge_cost(self.cfg, int(b.computed.sum()),
                                         b.rows + b.padded_rows,
                                         max(b.slot_widths))
            fl += f
            by_c += f / pk["bf16_flops_per_s"]
            by_m += by / pk["hbm_bytes_per_s"]
            least += self.least_s(f, by)
        return fl, least, ("memory" if by_m > by_c else "compute")

    def model_flops_per_row(self) -> float:
        """One row of a request through every slot's student and the
        merge."""
        f = sum(self.kind.slot_cost(self.cfg, a, w, 1)[0]
                for a, w in self.slots)
        return f + self.kind.merge_cost(self.cfg, len(self.slots), 1,
                                        max(w for _, w in self.slots))[0]


def reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
