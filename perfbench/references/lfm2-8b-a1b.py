"""LiquidAI LFM2-8B-A1B: gated short convolutions, grouped-query attention
with rotary positions and per-head QK-norm, two dense layers and then 32
routed experts a layer, 4 a token, at their published widths; the first 16
of its 24 layers (the configuration's file says why).  The plain reference
is the family's (``perfbench/models/lfm2_moe_lm.py``: float32, ``highest``
matmul precision, every expert over every row; no kernels, no cache, no
batching)."""
FAMILY = "lfm2_moe_lm"
BUILDER = "lfm2_moe_lm"
