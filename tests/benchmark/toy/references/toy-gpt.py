FAMILY = "gpt2_lm"
BUILDER = "gpt2_lm"
