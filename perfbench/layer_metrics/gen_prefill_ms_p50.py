"""Per-layer metric ``gen_prefill_ms_p50``: median duration of the ``gen:prefill`` spans in the window."""
from perfbench.harness.spans import gen_prefill_ms_p50 as read  # noqa: F401
