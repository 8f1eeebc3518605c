"""Reader of the sequence attention's cost inside a PREFILL (``ops/paged.py``:
``_contrib_DenseAttention`` and ``_contrib_WindowAttention`` under the graph
nodes ``layer<i>_attn``; the prefill programs ``jit_prefill_L<len>``).

``harness/window.py`` reads the lane forms of the two kinds of attention
inside the lane program's runs.  A prefill attends over the whole prompt at
once: since PR 52 one ``flash_fwd.N`` call a layer where the operands allow
it (``sequence_formulation``: grouped-query bfloat16 heads of whole lane
tiles over more than one query block, on a TPU), with the layout changes
around it under the same node; before it, and wherever the op keeps the XLA
form, a block's float32 scores between the products and the softmax.  Either
way the operations carry the node's name as a whole component of their scope
(the executor traces every node under its name), which is what this reader
matches; the gate after the attention is its own node (``layer<i>_attn_gate``)
and is not in it.

``seq_attn_prefill_ms``  device time of the operations under the nodes
    ``layer<i>_attn`` inside the runs of the prefill programs that start in
    the window, over the count of those runs (a mean over the window's mix
    of buckets, so it moves with the mix as well as with the code).

As every reader: the run's ``info`` in, a number out, or None where the trace
holds nothing for it (an untraced run, a rehearsal on the host, a program
without such nodes or without prefill programs on the device's ``XLA
Modules`` line).
"""
from perfbench.harness import spans as _spans
from perfbench.harness.moe_prefill import PREFILL_MODULE, ms_inside_runs

is_attention_op = _spans.in_scope(r"layer\d+_attn")


def seq_attn_prefill_ms(info):
    return ms_inside_runs(_spans.of_run(info), PREFILL_MODULE,
                          is_attention_op)
