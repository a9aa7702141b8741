"""Server: mean wall time of one ``serve_batch``, ms."""
from bench.metrics import _common


def read(run):
    return _common.dispatch_ms(run)
