"""On-chip benchmark of the DMF POI recommender: one cell per run, cells,
configurations, traffic mixes and per-layer metrics found by name (see
`perfbench/harness.py` and `BENCHMARK.json`)."""
