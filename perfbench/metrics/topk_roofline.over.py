"""Window top-k roofline share of the serving cells above the knee (moves serve_rps)."""
from perfbench.metrics._serve_readers import topk_roofline as read  # noqa: F401
