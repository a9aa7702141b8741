"""95th percentile of the latencies that ``p50_ms`` reads, ms. Not judged:
one host stall of a second in the window sets it (PERF.md)."""
from bench.measure import percentile


def read(run):
    return percentile(run.window.latencies_ms(), 95)
