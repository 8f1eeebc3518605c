"""Per-layer metric ``moe_shared_ms_per_step_pangu``: device time of the shared expert's two products and gate (traced under their nodes' names, ``layer<i>_shared_in`` / ``_gate`` / ``_out``) inside the runs of the lane program, over their count."""
from perfbench.harness.mla import moe_shared_ms_per_step as read  # noqa: F401
