"""Toy of the latent-attention family with a shared expert beside routed experts."""
FAMILY = "latent_moe_lm"
BUILDER = "latent_moe_lm"
