"""Per-layer metric ``window_attn_roofline_pct_laguna``: the rings a lane step fetches (the whole ring of a live lane) and the rows it writes over the device time under ``window_attention`` x the HBM's published rate."""
from perfbench.harness.window import window_attn_roofline_pct as read  # noqa: F401
