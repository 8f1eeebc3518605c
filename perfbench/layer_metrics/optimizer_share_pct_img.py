"""Per-layer metric ``optimizer_share_pct_img``: device time of the operations under the scope ``optimizer`` (the fused step's update loop) over busy time."""
from perfbench.harness.spans import optimizer_share_pct as read  # noqa: F401
