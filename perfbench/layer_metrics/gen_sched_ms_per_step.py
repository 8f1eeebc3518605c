"""Per-layer metric ``gen_sched_ms_per_step``: what is left of the engine thread per step: ``gen:forward`` (the dispatch), ``gen:grow`` / ``feed`` / ``emit``, the self time of ``gen:step`` and of ``gen:admit``."""
from perfbench.harness.spans import gen_sched_ms_per_step as read  # noqa: F401
