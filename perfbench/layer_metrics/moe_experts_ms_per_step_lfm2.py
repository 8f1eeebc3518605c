"""Per-layer metric ``moe_experts_ms_per_step_lfm2``: device time under the scope ``moe_experts`` over the ``gen:step`` count."""
from perfbench.harness.moe import moe_experts_ms_per_step as read  # noqa: F401
