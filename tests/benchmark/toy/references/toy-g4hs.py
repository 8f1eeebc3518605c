"""Toy of the hybrid state-space / attention family with routed experts beside a shared MLP."""
FAMILY = "granite_moe_hybrid_lm"
BUILDER = "granite_moe_hybrid_lm"
