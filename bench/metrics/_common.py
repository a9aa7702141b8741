"""Arithmetic shared by several readers."""
import numpy as np


def queue_ms(run):
    """Median time from due to dispatch of the dispatched requests, ms."""
    w = run.window
    sent = np.isfinite(w.t_dispatch)
    if not sent.any():
        return None
    return float(np.median(w.t_dispatch[sent] - w.t_arrival[sent])) * 1e3


def dispatch_ms(run):
    """Mean wall time of ``serve_batch`` (the engine's service time), ms."""
    b = [x.service_s for x in run.window.batches]
    return float(np.mean(b)) * 1e3 if b else None


def idle_share(run):
    """Share of the traced window in which no op ran on the chip, %."""
    t = run.trace
    if t is None or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


# the per-slot loop runs each student as the program ``jit_padded``
STUDENT_MODULES = r"^jit_padded"
# the quorum merge's Pallas kernel
MERGE_OPS = r"agg_kernel|quorum_aggregate"

