"""Median dispatch wall time of the serving cells above the knee (moves serve_rps)."""
from perfbench.metrics._serve_readers import dispatch_ms as read  # noqa: F401
