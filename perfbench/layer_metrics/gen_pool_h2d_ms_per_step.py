"""Per-layer metric ``gen_pool_h2d_ms_per_step``: total of the ``gen:pool_h2d`` spans (one ``device_put`` of the lanes' ids, positions, sources and page tables: no plane crosses) over the count of ``gen:step``."""
from perfbench.harness.spans import gen_pool_h2d_ms_per_step as read  # noqa: F401
