"""Request latency p95 of the serving cells above the knee, where the queue grows all through the window and the tail swings with the smallest change (moves serve_rps)."""
from perfbench.metrics._serve_readers import latency_p95_ms as read  # noqa: F401
