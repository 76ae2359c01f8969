"""Per-layer metric readers, one module each, named as in BENCHMARK.json."""
