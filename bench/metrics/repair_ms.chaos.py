"""Controller: mean planning time (``RepairOutcome.wall_s``) of the repairs
applied in the window, ms."""


def read(run):
    r = [x["wall_s"] for x in run.window.repairs if x["t"] <= run.seconds]
    return 1e3 * sum(r) / len(r) if r else None
