"""Whole step: model operations of the rows answered in the window (every
slot's student and the merge over each request's rows, padded rows left
out) over the window times the chip's bf16 peak, %."""


def read(run):
    w = run.window
    done = run.in_window(w.t_done)
    rows = float(w.sizes[done].sum())
    return 100.0 * rows * run.model_flops_per_row() / (
        run.seconds * run.peaks["bf16_flops_per_s"])
