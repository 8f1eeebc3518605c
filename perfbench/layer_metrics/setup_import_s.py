"""Per-layer metric ``setup_import_s``: the spans ``start:import`` (importing ``mxnet_tpu``) and ``start:backend`` (its first contact with the devices)."""
from perfbench.harness.startup import setup_import_s as read  # noqa: F401
