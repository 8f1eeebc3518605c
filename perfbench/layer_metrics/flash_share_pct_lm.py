"""Per-layer metric ``flash_share_pct_lm``: device time of the flash attention kernels over busy time."""
from perfbench.harness.readers import flash_share_pct as read  # noqa: F401
