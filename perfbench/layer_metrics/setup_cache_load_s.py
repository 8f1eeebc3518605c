"""Per-layer metric ``setup_cache_load_s``: the compile ledger's ``load`` rows under a ``start:program``: retrievals from the persistent compilation cache."""
from perfbench.harness.startup import setup_cache_load_s as read  # noqa: F401
