"""On-chip benchmark harness: one cell per run, driven by BENCHMARK.json."""
