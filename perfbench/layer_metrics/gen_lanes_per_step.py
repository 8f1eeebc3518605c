"""Per-layer metric ``gen_lanes_per_step``: tokens a decode step emits: (tokens - prefills) over decode steps, engine counters."""
from perfbench.harness.readers import gen_lanes_per_step as read  # noqa: F401
