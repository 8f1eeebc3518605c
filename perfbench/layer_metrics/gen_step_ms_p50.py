"""Per-layer metric ``gen_step_ms_p50``: median duration of the engine's ``gen:step`` spans in the window."""
from perfbench.harness.spans import gen_step_ms_p50 as read  # noqa: F401
