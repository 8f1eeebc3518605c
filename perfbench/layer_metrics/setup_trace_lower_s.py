"""Per-layer metric ``setup_trace_lower_s``: the compile ledger's ``trace`` and ``lower`` rows under a ``start:program``: what every start pays to find its cache keys."""
from perfbench.harness.startup import setup_trace_lower_s as read  # noqa: F401
