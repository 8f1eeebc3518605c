"""Per-layer metric ``gen_pool_copyback_ms_per_step``: total of the ``gen:pool_copyback`` spans (the host memcpy into the pool's planes) over the count of ``gen:step``."""
from perfbench.harness.spans import gen_pool_copyback_ms_per_step as read  # noqa: F401
