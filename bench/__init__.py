"""Chip benchmark of the RSKPCA system (see BENCHMARK.json and PERF.md)."""
