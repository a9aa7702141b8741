"""One reader per metric of BENCHMARK.json, found by the metric's name."""
