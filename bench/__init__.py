"""Benchmark of the served ensemble; see BENCHMARK.json and PERF.md."""
