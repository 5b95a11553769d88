"""Per-layer metrics, one reader per file; the file's name is the
metric's name in BENCHMARK.json and it exposes `compute(ctx)`."""
