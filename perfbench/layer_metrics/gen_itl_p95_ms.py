"""Per-layer metric ``gen_itl_p95_ms``: 95th percentile of the gaps between two tokens of one stream, client side."""
from perfbench.harness.readers import gen_itl_p95_ms as read  # noqa: F401
