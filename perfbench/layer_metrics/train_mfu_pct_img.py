"""Per-layer metric ``train_mfu_pct_img``: model FLOP/s (3 x 2 x forward multiply-adds an image) over the published peak."""
from perfbench.harness.readers import train_mfu_pct as read  # noqa: F401
