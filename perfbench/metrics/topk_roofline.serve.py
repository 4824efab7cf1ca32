"""Window top-k roofline share of the serving cells below the knee (moves serve_p95_ms)."""
from perfbench.metrics._serve_readers import topk_roofline as read  # noqa: F401
