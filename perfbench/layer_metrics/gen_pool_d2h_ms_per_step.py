"""Per-layer metric ``gen_pool_d2h_ms_per_step``: total of the ``gen:pool_d2h`` spans (the wait for the step dispatched one iteration back, then its lanes' picked ids, 32 bytes at 8 lanes: the engine thread's wait on the device) over the count of ``gen:step``."""
from perfbench.harness.spans import gen_pool_d2h_ms_per_step as read  # noqa: F401
