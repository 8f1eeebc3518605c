"""Per-layer metric ``compiles_in_window_lm``: compilations and cache loads JAX logged between the window's edges; must read 0."""
from perfbench.harness.readers import compiles_in_window as read  # noqa: F401
