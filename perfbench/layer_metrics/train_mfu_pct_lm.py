"""Per-layer metric ``train_mfu_pct_lm``: model FLOP/s (6ND + causal attention at the cut depth) over chips x the published peak."""
from perfbench.harness.readers import train_mfu_pct as read  # noqa: F401
