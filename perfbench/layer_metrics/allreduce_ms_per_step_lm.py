"""Per-layer metric ``allreduce_ms_per_step_lm``: device time of the collective operations in the trace, per step."""
from perfbench.harness.readers import allreduce_ms_per_step as read  # noqa: F401
