"""Engine: rows added by power-of-two bucketing over rows dispatched, %."""


def read(run):
    b = [x for x in run.window.batches if x.t_dispatch <= run.seconds]
    total = sum(x.rows + x.padded_rows for x in b)
    if not total:
        return None
    return 100.0 * sum(x.padded_rows for x in b) / total
