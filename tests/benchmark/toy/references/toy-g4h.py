FAMILY = "hybrid_lm"
BUILDER = "hybrid_lm"
