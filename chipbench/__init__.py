"""Chip benchmark for the X-TIME engine (see BENCHMARK.json and PERF.md)."""
