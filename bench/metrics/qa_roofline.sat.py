"""Kernels: least time of the quorum merges the traced batches ran over the
device time of the merge kernel's events, %."""
from bench.metrics import _common


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.op_time(_common.MERGE_OPS)
    if not n:
        return None
    _, least, _ = run.merge_counts(run.traced_batches())
    return 100.0 * least / secs
