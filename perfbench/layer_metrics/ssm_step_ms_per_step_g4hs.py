"""Per-layer metric ``ssm_step_ms_per_step_g4hs``: device time under the scope ``ssm_step`` over the ``gen:step`` count.  The reader is ``ssm_step_ms_per_step_g4h``'s: an entry of its own because ``tests/benchmark/test_cell_g4h_cpu.py`` pins that cell's entries by count."""
from perfbench.harness.ssm import ssm_step_ms_per_step as read  # noqa: F401
