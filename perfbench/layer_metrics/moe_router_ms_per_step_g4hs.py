"""Per-layer metric ``moe_router_ms_per_step_g4hs``: device time under the scope ``moe_router`` (either scoring rule: here the softmax over the picked logits) inside the runs of the lane program, over their count."""
from perfbench.harness.ssm_moe import moe_router_ms_per_step as read  # noqa: F401
