"""Per-layer metric ``flash_bwd_dq_ms_per_step_lm``: device time of the kernel ``flash_bwd_dq`` over the traced steps."""
from perfbench.harness.spans import flash_bwd_dq_ms_per_step as read  # noqa: F401
