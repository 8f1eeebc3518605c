"""Per-layer metric ``step_ms_p50_img``: median host-clock time of a traced step ending in a blocking read (image trainer)."""
from perfbench.harness.readers import step_ms_p50 as read  # noqa: F401
