"""openPangu-Ultra-MoE-718B: latent attention (128 heads of 128 | 64 | 128
over ranks 1536 / 512), sandwich norms, leading dense layers and then 256
routed experts a layer, 8 a token, beside one shared expert, an untied head,
at their published widths; one chip's share of sixteen of one dense and four
expert layers (the configuration's file says why).  The plain reference is
the family's (``perfbench/models/latent_moe_lm.py``: float32, ``highest``
matmul precision, every held expert over every row, the expanded attention
only; no kernels, no cache, no batching)."""
FAMILY = "latent_moe_lm"
BUILDER = "latent_moe_lm"
