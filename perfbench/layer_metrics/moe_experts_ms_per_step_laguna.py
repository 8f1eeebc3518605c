"""Per-layer metric ``moe_experts_ms_per_step_laguna``: device time under the scope ``moe_experts`` inside the runs of the lane program, over their count (the reader of ``moe_experts_ms_per_step_lfm2``, an entry of its own for the reason ``moe_experts_ms_per_step_pangu`` gives)."""
from perfbench.harness.moe import moe_experts_ms_per_step as read  # noqa: F401
