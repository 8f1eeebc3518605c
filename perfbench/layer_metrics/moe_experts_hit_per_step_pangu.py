"""Per-layer metric ``moe_experts_hit_per_step_pangu``: mean of ``gen:step``'s ``experts_hit`` (the experts held here that a live lane picked) over the expert layers."""
from perfbench.harness.mla import moe_experts_hit_per_step as read  # noqa: F401
