"""Per-layer metric ``setup_cache_misses``: count of backend compiles under a ``start:program``: 0 on a warm start, or a cache key moved."""
from perfbench.harness.startup import setup_cache_misses as read  # noqa: F401
