"""Per-layer metric ``allreduce_carrier_ms_per_step_lm``: device time, per step, of the fusions that carry a step of an asynchronous collective (computation ``async_collective_fusion.N``): compute and ring together."""
from perfbench.harness.collectives import allreduce_carrier_ms_per_step as read  # noqa: F401
