"""Cerebras-GPT 1.3B: a GPT-2 block stack at its published widths.  The
plain reference is the family's (``perfbench/models/gpt2_lm.py``: float32,
``highest`` matmul precision, no kernels, no cache, no batching)."""
FAMILY = "gpt2_lm"
BUILDER = "gpt2_lm"
