"""Students: least time of the student forwards the traced batches ran
(the larger of operations over peak FLOP/s and bytes over peak bytes/s,
per forward) over their summed device time, %."""
from bench.metrics import _common


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module_time(_common.STUDENT_MODULES)
    if not n:
        return None
    _, least = run.student_counts(run.traced_batches())
    return 100.0 * least / secs
