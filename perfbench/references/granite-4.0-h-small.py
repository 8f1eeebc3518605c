"""IBM Granite 4.0-H Small (32B-A9B): Mamba-2 layers of 128 heads and
grouped-query attention layers, in every layer 72 routed experts of width
768, ten a token by a softmax over the picked logits, beside a shared MLP of
1536, at their published widths; one chip's share of two of one period (10
layers; the configuration's file says why).  The plain reference is the
family's (``perfbench/models/granite_moe_hybrid_lm.py``: float32, ``highest``
matmul precision, the recurrence token by token, every held expert over
every row; no kernels, no chunks, no cache, no batching)."""
FAMILY = "granite_moe_hybrid_lm"
BUILDER = "granite_moe_hybrid_lm"
