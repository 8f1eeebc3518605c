"""Per-layer metric ``gen_paged_attn_share_pct``: device time of the operations under the scope ``paged_attention*`` over busy time."""
from perfbench.harness.spans import gen_paged_attn_share_pct as read  # noqa: F401
