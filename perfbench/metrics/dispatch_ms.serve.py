"""Median dispatch wall time of the serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import dispatch_ms as read  # noqa: F401
