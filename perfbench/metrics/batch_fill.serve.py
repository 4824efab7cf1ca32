"""Batch fill of the serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import batch_fill as read  # noqa: F401
