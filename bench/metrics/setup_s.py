"""Seconds from the process's start to the window's first dispatch:
weights, compiles and warm-up."""


def read(run):
    return run.setup_s
