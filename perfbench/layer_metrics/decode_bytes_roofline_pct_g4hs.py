"""Per-layer metric ``decode_bytes_roofline_pct_g4hs``: the bytes a lane step must move (weights outside the routed experts with the tied table once, the hit held experts, the lanes' state twice, their K/V rows) over the decode program's own device time x the HBM's published rate."""
from perfbench.harness.ssm_moe import decode_bytes_roofline_pct as read  # noqa: F401
