"""Reduces a profiler trace (``.xplane.pb``) to device and host intervals.

Device planes are those named ``/device:TPU:<n>``; on each, the ops line
(``XLA Ops``) gives the intervals in which an operation ran, and the
modules line (``XLA Modules``) the compiled programs. The host plane holds
the benchmark's own ``TraceAnnotation`` spans (``dispatch``,
``serve_batch``, ``poll_repair``, ``wait_due``) and the program's
(``engine.*`` and ``server.*``, ``PROGRAM_SPANS``; each micro-batch is one
``engine.batch`` span with the others nested in it), each program span
with the stats it was given (``engine.batch``'s ``rows`` and ``pad_rows``,
``server.merge``'s ``masked_slots``). Every time is in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

HOST_SPANS = ("wait_due", "poll_repair", "serve_batch", "dispatch")
PROGRAM_SPANS = (
    "engine.batch", "engine.control", "engine.inputs", "engine.device_wait",
    "engine.record", "server.draw", "server.stack", "server.slot_forward",
    "server.slot_mask", "server.merge", "server.decode_ops",
    "server.fused_step", "server.package")


class Span(NamedTuple):
    """One program span: its name, start and duration (s), and stats."""
    name: str
    t: float
    d: float
    stats: Optional[Dict] = None


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                   # traced window, seconds
    ops: Dict[int, List[Tuple[str, float, float]]]      # chip -> (name, t, d)
    modules: Dict[int, List[Tuple[str, float, float]]]  # chip -> (name, t, d)
    host: List[Tuple[str, float, float]]          # benchmark spans (t, d)
    # the program's spans by start time, the outer of two at one start first
    program: List[Span] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, chip: int) -> np.ndarray:
        """(n, 2) union of the intervals in which an op ran on ``chip``,
        clipped to the window."""
        return union([(t, t + d) for _, t, d in self.ops.get(chip, ())],
                     self.window)

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran anything."""
        chips = sorted(self.ops)
        if not chips:
            return 0.0
        return float(np.mean([float((self.busy(c)[:, 1]
                                     - self.busy(c)[:, 0]).sum())
                              for c in chips]))

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hits = [d for evs in self.ops.values() for n, _, d in evs
                if rx.search(n)]
        return float(sum(hits)), len(hits)

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of program runs whose name matches."""
        rx = re.compile(pattern)
        hits = [d for evs in self.modules.values() for n, _, d in evs
                if rx.search(n)]
        return float(sum(hits)), len(hits)

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.modules.values():
            for name, _, d in evs:
                tot[_base(name)] += d
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle seconds on the chips, by what the host was doing: the
        innermost benchmark span over each gap's midpoint, else the
        engine's own loop."""
        return self.idle_by_phase([], n)

    def idle_by_phase(self, spans: Optional[Sequence] = None,
                      n: int = 20) -> List[List]:
        """Idle seconds on the chips by the innermost program span over each
        gap's midpoint (the one that started last; program spans nest),
        else the benchmark span as ``idle_gaps`` chooses it, else
        ``engine_loop``. Sums to the window less the busy time, as
        ``idle_gaps`` does. ``spans`` defaults to ``program``."""
        spans = self.program if spans is None else spans
        host = sorted(self.host, key=lambda s: s[1])
        h_starts = np.asarray([s[1] for s in host])
        p_starts = np.asarray([s[1] for s in spans])
        p_ends = np.asarray([s[1] + s[2] for s in spans])
        tot: Dict[str, float] = defaultdict(float)
        for c in sorted(self.ops):
            b = self.busy(c)
            edges = [self.window[0]] + list(b.ravel()) + [self.window[1]]
            for lo, hi in zip(edges[0::2], edges[1::2]):
                if hi <= lo:
                    continue
                mid = 0.5 * (lo + hi)
                i = int(np.searchsorted(p_starts, mid, side="right"))
                j = max(0, i - 256)
                over = np.flatnonzero(p_ends[j:i] > mid)
                name = (spans[j + over[-1]][0] if over.size
                        else _benchmark_span(host, h_starts, mid)
                        or "engine_loop")
                tot[name] += (hi - lo) / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _benchmark_span(host: Sequence, starts: np.ndarray,
                    mid: float) -> Optional[str]:
    """The benchmark span ``Trace.idle_gaps`` puts the instant ``mid``
    under: the innermost of ``HOST_SPANS`` over it."""
    order = {name: i for i, name in enumerate(HOST_SPANS)}
    i = int(np.searchsorted(starts, mid, side="right"))
    best = None
    for name, t, d in host[max(0, i - 64):i]:
        if t <= mid < t + d and (best is None or order[name] < order[best]):
            best = name
    return best


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals: Sequence[Tuple[float, float]],
          clip: Tuple[float, float]) -> np.ndarray:
    """Sorted, disjoint union of ``intervals`` inside ``clip``."""
    iv = sorted((max(a, clip[0]), min(b, clip[1])) for a, b in intervals)
    out: List[List[float]] = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out, np.float64).reshape(-1, 2)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, window: Optional[Tuple[float, float]] = None) -> Trace:
    """Read ``path``. ``window`` (seconds, on the trace's clock) defaults to
    the extent of the benchmark's host spans, else of the program's (a
    trace of a live engine holds no benchmark spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host: List[Tuple[str, float, float]] = []
    program: List[Span] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [(e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
                    elif e.name in PROGRAM_SPANS:
                        program.append(Span(e.name, e.start_ns * 1e-9,
                                            e.duration_ns * 1e-9,
                                            dict(e.stats)))
    program.sort(key=lambda s: (s.t, -s.d))
    if window is None:
        extent = host or program
        if not extent:
            raise ValueError("the trace holds none of the benchmark's or the "
                             "program's spans")
        window = (min(s[1] for s in extent),
                  max(s[1] + s[2] for s in extent))
    return Trace(window, ops, modules, host, program)
