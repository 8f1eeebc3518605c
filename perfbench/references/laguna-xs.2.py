"""Laguna-XS.2: 40 layers that repeat full, sliding, sliding, sliding; 8 K/V
heads of 128 under 48 query heads in a full layer (half of a head rotated by
YaRN's table) and 64 in a sliding one (window 512, plain angles), a gate a
head, one dense layer and then 256 routed experts of 512, 8 a token, beside
one shared expert, an untied head, at their published widths; one chip's
share of eight of layers 0-19 (the configuration's file says why).  The plain
reference is the family's (``perfbench/models/laguna_lm.py``: float32,
``highest`` matmul precision, the whole sequence under an explicit mask,
every held expert over every row; no kernels, no ring, no pages, no
batching)."""
FAMILY = "laguna_lm"
BUILDER = "laguna_lm"
