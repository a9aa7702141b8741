"""Failure draws: host time of the span ``server.draw`` (each slot's
arrival drawn from the failure model) in one micro-batch, ms: its median
over the ``engine.batch`` spans that start in the traced window."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    return program_spans.per_batch_ms(run.trace, ("server.draw",))
