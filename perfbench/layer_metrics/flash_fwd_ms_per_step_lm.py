"""Per-layer metric ``flash_fwd_ms_per_step_lm``: device time of the kernel ``flash_fwd`` over the traced steps."""
from perfbench.harness.spans import flash_fwd_ms_per_step as read  # noqa: F401
