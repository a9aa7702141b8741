"""Device: time in which an op ran on the chip inside one micro-batch, ms:
the union of the trace's device ops within each ``engine.batch`` span that
starts in the traced window, its median over those batches."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    return program_spans.device_per_batch_ms(run.trace)
