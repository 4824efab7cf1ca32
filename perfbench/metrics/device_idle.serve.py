"""Device idle share of the serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import device_idle as read  # noqa: F401
