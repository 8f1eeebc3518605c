"""Per-layer metric ``ssm_scan_share_pct_g4hs``: device time of the operations under the scope ``ssm_scan`` (the prefill's convolution and chunked scan, here at 128 heads and prompts to 2,048) over busy time."""
from perfbench.harness.ssm import ssm_scan_share_pct as read  # noqa: F401
