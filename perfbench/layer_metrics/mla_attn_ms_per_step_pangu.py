"""Per-layer metric ``mla_attn_ms_per_step_pangu``: device time under the scope ``paged_attention_latent`` (the absorbed latent attention of a decode step) inside the runs of the lane program, over their count."""
from perfbench.harness.mla import mla_attn_ms_per_step as read  # noqa: F401
