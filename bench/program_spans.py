"""The program's own host spans in a profiler trace, per batch and per gap.

The engine and the server annotate their host phases with
``jax.profiler.TraceAnnotation`` (``engine.*`` and ``server.*``, listed in
``PROGRAM_SPANS``); each micro-batch is one ``engine.batch`` span with the
others nested in it. They land on the host plane of the same ``.xplane.pb``
as the device ops, on the same clock. This module reads them beside what
``trace_reduce`` reads:

- ``per_batch_ms``: the time of some phases per micro-batch;
- ``idle_by_phase``: the chip's idle time by the innermost program span
  over each gap, else by the benchmark's span as ``Trace.idle_gaps`` puts
  it, else the engine's loop.

A trace of a program without these spans gives no spans, and every reading
is then None or empty. Run alone, it prints the split of one trace:

    python3 bench/program_spans.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

PROGRAM_SPANS = (
    "engine.batch", "engine.control", "engine.inputs", "engine.device_wait",
    "engine.record", "server.draw", "server.stack", "server.slot_forward",
    "server.slot_mask", "server.merge", "server.decode_ops",
    "server.fused_step", "server.package")
# the per-slot loop: its forwards, eager masks and the merge launch
SLOT_LOOP = ("server.slot_forward", "server.slot_mask", "server.merge")

Span = Tuple[str, float, float]          # (name, start s, duration s)


def load_spans(path: str) -> List[Span]:
    """The program spans of the trace's host planes, by start time."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    out.append((e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def load(path: str) -> Tuple[trace_reduce.Trace, List[Span]]:
    """``trace_reduce.load(path)`` and the program spans. The window is the
    benchmark spans' extent, else the program spans' (a trace of a live
    engine holds no benchmark spans)."""
    spans = load_spans(path)
    try:
        return trace_reduce.load(path), spans
    except ValueError:
        if not spans:
            raise
        window = (spans[0][1], max(t + d for _, t, d in spans))
        return trace_reduce.load(path, window), spans


def batches_in(trace: trace_reduce.Trace, spans: Sequence[Span]) -> int:
    """``engine.batch`` spans that start inside the traced window."""
    lo, hi = trace.window
    return sum(1 for n, t, _ in spans
               if n == "engine.batch" and lo <= t < hi)


def per_batch_ms(trace: trace_reduce.Trace, spans: Sequence[Span],
                 names: Sequence[str]) -> Optional[float]:
    """Summed durations of the ``names`` spans, clipped to the window, per
    ``engine.batch`` that starts in it, ms; None without program spans."""
    n = batches_in(trace, spans)
    if not n:
        return None
    lo, hi = trace.window
    secs = sum(max(0.0, min(t + d, hi) - max(t, lo))
               for name, t, d in spans if name in names)
    return 1e3 * secs / n


def phase_split(trace: trace_reduce.Trace,
                spans: Sequence[Span]) -> Dict[str, float]:
    """Milliseconds per batch of every program span that occurs."""
    names = sorted({s[0] for s in spans}, key=PROGRAM_SPANS.index)
    return {n: per_batch_ms(trace, spans, (n,)) for n in names}


def _benchmark_span(host: Sequence[Span], starts: np.ndarray,
                    mid: float) -> Optional[str]:
    """The benchmark span ``Trace.idle_gaps`` puts the instant ``mid``
    under: the innermost of ``trace_reduce.HOST_SPANS`` over it."""
    order = {name: i for i, name in enumerate(trace_reduce.HOST_SPANS)}
    i = int(np.searchsorted(starts, mid, side="right"))
    best = None
    for name, t, d in host[max(0, i - 64):i]:
        if t <= mid < t + d and (best is None or order[name] < order[best]):
            best = name
    return best


def idle_by_phase(trace: trace_reduce.Trace, spans: Sequence[Span],
                  n: int = 20) -> List[List]:
    """Idle seconds on the chips by the innermost program span over each
    gap's midpoint (the one that started last; program spans nest), else
    the benchmark span as ``Trace.idle_gaps`` chooses it, else
    ``engine_loop``. Sums to the window less the busy time, as
    ``idle_gaps`` does."""
    host = sorted(trace.host, key=lambda s: s[1])
    h_starts = np.asarray([s[1] for s in host])
    p_starts = np.asarray([s[1] for s in spans])
    p_ends = np.asarray([s[1] + s[2] for s in spans])
    tot: Dict[str, float] = defaultdict(float)
    for c in sorted(trace.ops):
        b = trace.busy(c)
        edges = [trace.window[0]] + list(b.ravel()) + [trace.window[1]]
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
            tot[name] += (hi - lo) / len(trace.ops)
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:n]]


def report(path: str) -> Dict:
    """Everything this module reads from one trace, for a log line."""
    trace, spans = load(path)
    return {"window_s": trace.window_s, "busy_s": trace.busy_s(),
            "batches": batches_in(trace, spans),
            "slot_loop_ms": per_batch_ms(trace, spans, SLOT_LOOP),
            "ms_per_batch": phase_split(trace, spans),
            "idle_gaps": trace.idle_gaps(),
            "idle_gaps_by_phase": idle_by_phase(trace, spans)}


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(report(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
