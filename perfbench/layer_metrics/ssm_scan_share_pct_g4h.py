"""Per-layer metric ``ssm_scan_share_pct_g4h``: device time of the operations under the scope ``ssm_scan`` (the prefill's convolution and chunked scan) over busy time."""
from perfbench.harness.ssm import ssm_scan_share_pct as read  # noqa: F401
