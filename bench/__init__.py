"""The benchmark: see BENCHMARK.json at the root and bench/harness.py."""
