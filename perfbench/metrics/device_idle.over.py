"""Device idle share of the serving cells above the knee (moves serve_rps)."""
from perfbench.metrics._serve_readers import device_idle as read  # noqa: F401
