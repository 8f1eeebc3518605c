"""Per-layer metric ``step_host_ms_p50_img``: median per step of the ``Module.forward_backward`` + ``Module.update`` spans: the program's host side of a training step."""
from perfbench.harness.spans import step_host_ms_p50 as read  # noqa: F401
