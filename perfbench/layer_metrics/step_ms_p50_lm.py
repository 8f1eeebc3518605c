"""Per-layer metric ``step_ms_p50_lm``: median host-clock time of a traced step ending in a blocking read (LM trainer)."""
from perfbench.harness.readers import step_ms_p50 as read  # noqa: F401
