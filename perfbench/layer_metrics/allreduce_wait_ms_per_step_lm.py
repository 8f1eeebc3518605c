"""Per-layer metric ``allreduce_wait_ms_per_step_lm``: the core's time held by collectives, per step: the synchronous operations and the ``async-collective-done`` (or ``-done``) waits of the asynchronous ones."""
from perfbench.harness.collectives import allreduce_wait_ms_per_step as read  # noqa: F401
