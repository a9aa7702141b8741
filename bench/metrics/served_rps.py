"""Requests answered inside the window, per second of the window."""


def read(run):
    w = run.window
    return float(run.in_window(w.t_done).sum()) / run.seconds
