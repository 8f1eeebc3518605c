"""Per-layer metric ``decode_bytes_roofline_pct_lfm2``: the bytes a lane step must move (weights outside the experts, the hit experts, live pages, tails) over the decode program's own device time x the HBM's published rate."""
from perfbench.harness.moe import decode_bytes_roofline_pct as read  # noqa: F401
