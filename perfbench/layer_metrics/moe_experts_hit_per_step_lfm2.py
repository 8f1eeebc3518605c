"""Per-layer metric ``moe_experts_hit_per_step_lfm2``: mean of ``gen:step``'s ``experts_hit`` over the expert layers: the experts at least one live lane picked, a layer and step."""
from perfbench.harness.moe import moe_experts_hit_per_step as read  # noqa: F401
