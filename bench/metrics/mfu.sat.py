"""Whole step: model operations of the images answered in the window
(every slot's student and the merge, padded rows left out) over the
window times the chip's bf16 peak, %."""


def read(run):
    w = run.window
    done = run.in_window(w.t_done)
    images = float(w.sizes[done].sum())
    return 100.0 * images * run.model_flops_per_image() / (
        run.seconds * run.peaks["bf16_flops_per_s"])
