"""Per-layer metric ``gen_ttft_p50_ms``: median time from sending a request to its first token, client side."""
from perfbench.harness.readers import gen_ttft_p50_ms as read  # noqa: F401
