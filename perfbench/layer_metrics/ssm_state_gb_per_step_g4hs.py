"""Per-layer metric ``ssm_state_gb_per_step_g4hs``: mean of ``gen:step``'s ``state_bytes``: the recurrent state of the step's lanes, which the step reads once and writes once."""
from perfbench.harness.ssm import ssm_state_gb_per_step as read  # noqa: F401
