"""Per-layer metric ``setup_bind_s``: self time of the spans ``start:bind``, ``start:pool`` and ``start:optimizer``: binding executors, allocating the pool's planes, installing the optimizer."""
from perfbench.harness.startup import setup_bind_s as read  # noqa: F401
