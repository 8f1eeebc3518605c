"""Per-layer metric ``moe_prefill_experts_ms_g4hs``: device time under the scope ``moe_experts`` (sort, the movement of the pairs' rows, both grouped products, gate, combine) inside the runs of the prefill programs ``jit_prefill_L*``, over the count of those runs."""
from perfbench.harness.moe_prefill import moe_prefill_experts_ms as read  # noqa: F401
