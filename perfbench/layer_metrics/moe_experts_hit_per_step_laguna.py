"""Per-layer metric ``moe_experts_hit_per_step_laguna``: mean of ``gen:step``'s ``experts_hit`` (the experts held here that a live lane picked) over the sparse layers."""
from perfbench.harness.window import moe_experts_hit_per_step as read  # noqa: F401
