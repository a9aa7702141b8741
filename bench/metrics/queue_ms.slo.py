"""Engine: median time from a request's due time to its dispatch, ms."""
from bench.metrics import _common


def read(run):
    return _common.queue_ms(run)
