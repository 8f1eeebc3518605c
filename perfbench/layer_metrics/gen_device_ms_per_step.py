"""Per-layer metric ``gen_device_ms_per_step``: the lane program's own time on the device: the mean duration of the runs of ``jit_decode_b<lanes>`` on the ``XLA Modules`` line that start in the window (a step in flight runs on outside every ``gen:step`` span, so no span bounds it)."""
from perfbench.harness.spans import gen_device_ms_per_step as read  # noqa: F401
