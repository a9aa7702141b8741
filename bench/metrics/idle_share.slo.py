"""Device: share of the traced window with no op running on the chip, %."""
from bench.metrics import _common


def read(run):
    return _common.idle_share(run)
