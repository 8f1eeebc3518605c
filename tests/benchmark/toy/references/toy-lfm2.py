"""Toy of the short-convolution / attention family with routed experts."""
FAMILY = "lfm2_moe_lm"
BUILDER = "lfm2_moe_lm"
