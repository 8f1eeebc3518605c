"""Per-layer metric ``moe_experts_ms_per_step_lfm2``: device time under the scope ``moe_experts`` inside the runs of the lane program, over their count (a prefill's expert time is not a step's)."""
from perfbench.harness.moe import moe_experts_ms_per_step as read  # noqa: F401
