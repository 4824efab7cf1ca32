"""Client queue p95 of the serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import queue_p95_ms as read  # noqa: F401
