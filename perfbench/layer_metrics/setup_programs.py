"""Per-layer metric ``setup_programs``: number of ``start:program`` spans: the programs a start builds."""
from perfbench.harness.startup import setup_programs as read  # noqa: F401
