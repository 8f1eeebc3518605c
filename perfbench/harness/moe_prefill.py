"""Reader of the routed expert layers' cost inside a PREFILL (``ops/moe.py``:
the scope ``moe_experts``; the prefill programs ``jit_prefill_L<len>``).

``harness/moe.py``'s ``moe_experts_ms_per_step`` reads the scope inside the
lane program's runs: a lane step's 64-160 pairs are one row tile, and what
the layer costs there is its hit experts' bytes.  A prefill's thousands of
pairs cost something else: the kernel ``moe_grouped`` and, around it, the
movement of the pairs' rows into sorted order and back (on a chip that holds
a share of the experts, since PR 45, of the held pairs' rows alone: the
gather loop before the kernel and ``moe_combine`` after it, under the same
scope).  This reader keeps that in sight:

``moe_prefill_experts_ms``  device time of the operations under
    ``moe_experts`` inside the runs of the prefill programs that start in
    the window, over the count of those runs (a mean over the window's mix
    of buckets: 10 : 5 : 1 of 2,048 : 1,024 : 512 in the long-prompt mix).

As every reader: the run's ``info`` in, a number out, or None where the
trace holds nothing for it (an untraced run, a rehearsal on the host, a
program without the scope or without prefill programs on the device's
``XLA Modules`` line).
"""
from perfbench.harness import spans as _spans
from perfbench.harness.moe import is_expert_op

PREFILL_MODULE = "jit_prefill_L"


def ms_inside_runs(trace, part, match):
    """Device time, in ms a run, of the operations ``match`` picks inside
    the runs of the programs whose name holds ``part`` (those that start in
    the window), or None where there is no such run or no such time."""
    runs = _spans.module_runs(trace, part) if trace else {}
    secs, n = 0.0, 0
    for plane, spans in runs.items():
        j, n = 0, n + len(spans)
        for op in trace.devices.get(plane, ()):
            while j < len(spans) and spans[j][1] <= op.start:
                j += 1
            if j < len(spans) and spans[j][0] <= op.start and match(op):
                secs += op.end - op.start
    return 1e3 * secs / n if n and secs > 0 else None


def moe_prefill_experts_ms(info):
    return ms_inside_runs(_spans.of_run(info), PREFILL_MODULE, is_expert_op)
