"""Per-layer metric ``ssm_step_ms_per_step_g4h``: device time under the scope ``ssm_step`` over the ``gen:step`` count."""
from perfbench.harness.ssm import ssm_step_ms_per_step as read  # noqa: F401
