"""Server: host time of the per-slot loop in one micro-batch, ms: the
spans ``server.slot_forward`` (the K slot dispatches) and ``server.merge``
(the mask-and-stack program and the merge launch) inside each
``engine.batch`` that starts in the traced window, their median over
those batches."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    return program_spans.per_batch_ms(run.trace, program_spans.SLOT_LOOP)
