"""IBM Granite 4.0-H Micro: 36 Mamba-2 layers and 4 grouped-query attention
layers at their published widths.  The plain reference is the family's
(``perfbench/models/hybrid_lm.py``: float32, ``highest`` matmul precision,
the recurrence token by token; no kernels, no chunks, no cache, no
batching)."""
FAMILY = "hybrid_lm"
BUILDER = "hybrid_lm"
