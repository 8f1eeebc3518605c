"""Per-layer metric ``window_attn_ms_per_step_laguna``: device time under the scope ``window_attention`` (the sliding layers' attention over a lane's ring) inside the runs of the lane program, over their count."""
from perfbench.harness.window import window_attn_ms_per_step as read  # noqa: F401
