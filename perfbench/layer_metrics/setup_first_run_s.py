"""Per-layer metric ``setup_first_run_s``: the ``start:program`` spans less their ledger rows: dispatch and first execution."""
from perfbench.harness.startup import setup_first_run_s as read  # noqa: F401
