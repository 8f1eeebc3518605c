"""Per-layer metric ``setup_compile_s``: the compile ledger's ``compile`` rows under a ``start:program``: backend compiles that ran; near 0 on a warm start."""
from perfbench.harness.startup import setup_compile_s as read  # noqa: F401
