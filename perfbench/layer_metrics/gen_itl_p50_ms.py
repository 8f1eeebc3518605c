"""Per-layer metric ``gen_itl_p50_ms``: median gap between two tokens of one stream, client side."""
from perfbench.harness.readers import gen_itl_p50_ms as read  # noqa: F401
