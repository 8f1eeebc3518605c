"""Per-layer metric ``moe_experts_share_pct_lfm2``: device time of the operations under the scope ``moe_experts`` (sort, both grouped products, gate, combine) over busy time."""
from perfbench.harness.moe import moe_experts_share_pct as read  # noqa: F401
