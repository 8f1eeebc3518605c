"""Per-layer metric ``gen_device_ms_per_step``: device busy time inside the ``gen:step`` spans over their count."""
from perfbench.harness.spans import gen_device_ms_per_step as read  # noqa: F401
