"""Per-layer metric ``decode_bytes_roofline_pct_pangu``: the bytes a lane step must read (weights outside the routed experts but the embedding table, the hit held experts, the live latent rows) over the decode program's own device time x the HBM's published rate."""
from perfbench.harness.mla import decode_bytes_roofline_pct as read  # noqa: F401
