"""Batch fill of the serving cells above the knee (moves serve_rps)."""
from perfbench.metrics._serve_readers import batch_fill as read  # noqa: F401
