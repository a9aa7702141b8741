"""Share of the requests due in the window answered with every partition
present (degraded, unanswered and failed requests are misses), in %."""
import numpy as np


def read(run):
    w = run.window
    ok = w.quorum_ok & np.isfinite(w.t_done)
    return 100.0 * float(ok.sum()) / max(w.due, 1)
