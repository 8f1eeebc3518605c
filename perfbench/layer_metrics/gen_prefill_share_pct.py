"""Per-layer metric ``gen_prefill_share_pct``: share of the streams' waiting that is another request's prefill, from the client-side gaps."""
from perfbench.harness.readers import gen_prefill_share_pct as read  # noqa: F401
