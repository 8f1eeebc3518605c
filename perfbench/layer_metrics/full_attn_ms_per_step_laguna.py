"""Per-layer metric ``full_attn_ms_per_step_laguna``: device time under the scope ``paged_attention`` (the full layers' attention over pages) inside the runs of the lane program, over their count."""
from perfbench.harness.window import full_attn_ms_per_step as read  # noqa: F401
