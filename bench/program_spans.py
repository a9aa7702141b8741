"""The program's own host spans in a profiler trace, per batch and per gap.

The engine and the server annotate their host phases with
``jax.profiler.TraceAnnotation`` (``engine.*`` and ``server.*``, listed in
``trace_reduce.PROGRAM_SPANS``); each micro-batch is one ``engine.batch``
span with the others nested in it. They land on the host plane of the same
``.xplane.pb`` as the device ops, on the same clock, and
``trace_reduce.load`` keeps them as ``Trace.program``. This module reads
them:

- ``per_batch_ms``: the median time of some phases in a micro-batch;
- ``device_per_batch_ms``: the median time the chip was busy in one;
- ``phase_split``: the mean time of every phase in a micro-batch;
- ``Trace.idle_by_phase``: the chip's idle time by the innermost program
  span over each gap, else by the benchmark's span as ``Trace.idle_gaps``
  puts it, else the engine's loop.

A trace of a program without these spans gives no spans, and every reading
is then None or empty. Run alone, it prints the split of one trace:

    python3 bench/program_spans.py <trace dir or .xplane.pb>
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import PROGRAM_SPANS  # noqa: E402

# the per-slot loop: its forwards and the merge launch
SLOT_LOOP = ("server.slot_forward", "server.merge")


def batches(trace: trace_reduce.Trace) -> List[trace_reduce.Span]:
    """``engine.batch`` spans that start inside the traced window."""
    lo, hi = trace.window
    return [s for s in trace.program
            if s.name == "engine.batch" and lo <= s.t < hi]


def per_batch_s(trace: trace_reduce.Trace,
                names: Sequence[str]) -> np.ndarray:
    """Seconds of the ``names`` spans that start inside each ``engine.batch``
    of ``batches``, clipped to the window, one entry a batch."""
    hi = trace.window[1]
    mine = [s for s in trace.program if s.name in names]
    starts = np.asarray([s.t for s in mine])
    ends = np.cumsum([0.0] + [max(0.0, min(s.t + s.d, hi) - s.t)
                              for s in mine])
    out = []
    for b in batches(trace):
        i, j = np.searchsorted(starts, [b.t, b.t + b.d], side="left")
        out.append(ends[j] - ends[i])
    return np.asarray(out, np.float64)


def per_batch_ms(trace: trace_reduce.Trace,
                 names: Sequence[str]) -> Optional[float]:
    """Median over the traced batches of the time of their ``names`` spans,
    ms; None without program spans."""
    secs = per_batch_s(trace, names)
    return 1e3 * float(np.median(secs)) if secs.size else None


def device_per_batch_ms(trace: trace_reduce.Trace) -> Optional[float]:
    """Median over the traced batches of the time an op ran on the chip
    inside each ``engine.batch`` span (the union of the ops, averaged over
    the chips), ms; None without program spans or device ops."""
    bs = batches(trace)
    if not bs or not trace.ops:
        return None
    per = np.zeros(len(bs))
    for c in sorted(trace.ops):
        busy = trace.busy(c)
        for k, b in enumerate(bs):
            lo, hi = b.t, b.t + b.d
            i = int(np.searchsorted(busy[:, 1], lo, side="right"))
            j = int(np.searchsorted(busy[:, 0], hi, side="left"))
            iv = busy[i:j]
            per[k] += float((np.minimum(iv[:, 1], hi)
                             - np.maximum(iv[:, 0], lo)).sum())
    return 1e3 * float(np.median(per / len(trace.ops)))


def phase_split(trace: trace_reduce.Trace) -> Dict[str, float]:
    """Mean milliseconds per batch of every program span that occurs, so
    that the phases add up to ``engine.batch``."""
    names = sorted({s.name for s in trace.program}, key=PROGRAM_SPANS.index)
    return {n: 1e3 * float(np.mean(per_batch_s(trace, (n,))))
            for n in names}


def report(path: str) -> Dict:
    """Everything this module reads from one trace, for a log line."""
    trace = trace_reduce.load(path)
    return {"window_s": trace.window_s, "busy_s": trace.busy_s(),
            "batches": len(batches(trace)),
            "slot_loop_ms": per_batch_ms(trace, SLOT_LOOP),
            "device_ms": device_per_batch_ms(trace),
            "ms_per_batch": phase_split(trace),
            "idle_gaps": trace.idle_gaps(),
            "idle_gaps_by_phase": trace.idle_by_phase()}


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
