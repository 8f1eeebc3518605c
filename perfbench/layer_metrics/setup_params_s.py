"""Per-layer metric ``setup_params_s``: the spans ``start:params``: initialising or placing the parameters on the device."""
from perfbench.harness.startup import setup_params_s as read  # noqa: F401
