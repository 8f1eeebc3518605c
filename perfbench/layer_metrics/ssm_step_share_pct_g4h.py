"""Per-layer metric ``ssm_step_share_pct_g4h``: device time of the operations under the scope ``ssm_step`` (the state-space layers' convolution and recurrence steps) over busy time."""
from perfbench.harness.ssm import ssm_step_share_pct as read  # noqa: F401
