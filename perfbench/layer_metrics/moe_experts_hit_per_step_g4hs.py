"""Per-layer metric ``moe_experts_hit_per_step_g4hs``: mean of ``gen:step``'s ``experts_hit`` (the experts held here that a live lane picked) over the expert layers, which here are all of them."""
from perfbench.harness.moe import moe_experts_hit_per_step as read  # noqa: F401
