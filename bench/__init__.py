"""Benchmark of FailLite's serving path on the chip (see BENCHMARK.json)."""
