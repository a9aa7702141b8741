"""Median latency over every request due in the window, in ms: the real
time of its answer minus its due time; an unanswered request is +inf."""
from bench.measure import percentile


def read(run):
    return percentile(run.window.latencies_ms(), 50)
