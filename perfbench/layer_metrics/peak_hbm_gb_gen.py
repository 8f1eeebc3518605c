"""Per-layer metric ``peak_hbm_gb_gen``: peak_bytes_in_use of the fullest chip."""
from perfbench.harness.readers import peak_hbm_gb as read  # noqa: F401
