"""Whole serve step share of peak, serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import mfu as read  # noqa: F401
