"""The benchmark: a harness that runs one cell of BENCHMARK.json (run.py),
its yardstick (flops.py, peaks/, trace_reduce.py, reference.py) and the data
files that define configurations, traffic mixes and per-layer metrics."""
