"""Whole serve step share of peak, serving cells above the knee (moves serve_rps)."""
from perfbench.metrics._serve_readers import mfu as read  # noqa: F401
