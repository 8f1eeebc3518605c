"""Toy of the family with sliding-window layers beside global ones."""
FAMILY = "laguna_lm"
BUILDER = "laguna_lm"
