"""Controller: slots whose compiled forward a migration in the window
dropped (refit slots, or every slot when the uniform width changed)."""


def read(run):
    r = [x for x in run.window.repairs if x["t"] <= run.seconds]
    return float(sum(x["rejitted"] for x in r)) if r else None
