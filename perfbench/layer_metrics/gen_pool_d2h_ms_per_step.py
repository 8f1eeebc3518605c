"""Per-layer metric ``gen_pool_d2h_ms_per_step``: total of the ``gen:pool_d2h`` spans (the blocking reads: the device's own work, then the planes back) over the count of ``gen:step``."""
from perfbench.harness.spans import gen_pool_d2h_ms_per_step as read  # noqa: F401
