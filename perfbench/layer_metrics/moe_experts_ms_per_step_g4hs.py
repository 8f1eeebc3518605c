"""Per-layer metric ``moe_experts_ms_per_step_g4hs``: device time under the scope ``moe_experts`` inside the runs of the lane program, over their count.  The reader is ``moe_experts_ms_per_step_lfm2``'s: an entry of its own because ``tests/benchmark/test_cell_lfm2_cpu.py`` pins that cell's entries by count."""
from perfbench.harness.moe import moe_experts_ms_per_step as read  # noqa: F401
