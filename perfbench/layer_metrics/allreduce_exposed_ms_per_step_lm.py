"""Per-layer metric ``allreduce_exposed_ms_per_step_lm``: the part of the collectives' time during which nothing else runs on that device, per step."""
from perfbench.harness.readers import allreduce_exposed_ms_per_step as read  # noqa: F401
