"""Per-layer metric ``device_idle_share_img``: 1 - union of device operation intervals over the traced window."""
from perfbench.harness.readers import device_idle_share as read  # noqa: F401
