"""Per-layer metric ``gen_pool_h2d_ms_per_step``: total of the ``gen:pool_h2d`` spans (the ``set_input`` loop: the KV planes host to device) over the count of ``gen:step``."""
from perfbench.harness.spans import gen_pool_h2d_ms_per_step as read  # noqa: F401
