"""Per-layer metric ``window_gb_per_step_laguna``: mean of ``gen:step``'s ``window_bytes`` (the live lanes' rings over every sliding layer), in GB."""
from perfbench.harness.window import window_gb_per_step as read  # noqa: F401
