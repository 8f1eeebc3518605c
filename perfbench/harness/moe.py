"""Readers of the routed expert layers' per-layer metrics (``ops/moe.py``:
scopes ``moe_router`` and ``moe_experts``; ``generation/engine.py``: the
``experts_hit`` / ``expert_pairs`` / ``expert_bytes`` of a ``gen:step``).  As
every reader: the run's ``info`` in, a number out, or None where the trace
holds nothing for it (an untraced run, a rehearsal on the host, a program
without these scopes and span arguments).

Since PR 37 a layer's two grouped products and its gate are the repo's own
kernel, one call a layer (``moe_grouped.N``), and carry the scope they were
traced under, so ``moe_experts`` holds the layer's whole cost.  Under the
``"ragged"`` formulation (operands the kernel does not take) the products are
XLA's kernel, whose custom calls reach the trace as ``ragged-dot-none.N``
WITHOUT that scope (the compiler names the rewritten operation anew: my chip
runs, PR 34): the expert layer's device time is the operations under
``moe_experts`` plus the events of those names.

``decode_bytes_roofline_pct`` divides the bytes a lane step must read by the
decode program's WHOLE device time (``gen_device_ms_per_step``: its runs on
the device's ``XLA Modules`` line), not by the ``moe_experts`` scope: XLA
prefetches weights under waits that carry no scope (PERF.md section 5), and
bytes over a scope that misses them would read over 100 %.  The bytes count
each HIT expert's weights once a layer, which is what ``moe_grouped`` fetches
for the 64 pairs of a lane step (one row tile: one visit an expert).
"""
import math
import re
import statistics

from perfbench.harness import peaks
from perfbench.harness import spans as _spans

EXPERT_LEAVES = ("experts_w13", "experts_w2")
_GROUPED_RE = re.compile(r"^ragged-dot-")
_IN_SCOPE = _spans.in_scope("moe_experts")


def is_expert_op(op):
    """An operation of the expert layer: traced under ``moe_experts``, or
    one of XLA's grouped-product calls (module docstring)."""
    return _IN_SCOPE(op) or bool(_GROUPED_RE.match(op.name))


def _experts_s(info):
    """(trace, the expert layers' device seconds in the window) or (None,
    0)."""
    tr = _spans.of_run(info)
    return (tr, _spans.op_s(tr, is_expert_op)) if tr else (None, 0.0)


def moe_experts_share_pct(info):
    tr, secs = _experts_s(info)
    busy = _spans.busy_s(tr) if tr else 0.0
    return 100.0 * secs / busy if busy > 0 and secs > 0 else None


def moe_experts_ms_per_step(info):
    """The expert layers' device time inside the decode program's runs, over
    their count (a prefill's expert time is not a step's)."""
    tr = _spans.of_run(info)
    runs = _spans.module_runs(tr, _spans.DECODE_MODULE) if tr else {}
    if not runs:
        return None
    secs, n = 0.0, 0
    for plane, spans in runs.items():
        j, n = 0, n + len(spans)
        for op in tr.devices.get(plane, ()):
            while j < len(spans) and spans[j][1] <= op.start:
                j += 1
            if j < len(spans) and spans[j][0] <= op.start and \
                    is_expert_op(op):
                secs += op.end - op.start
    return 1e3 * secs / n if n and secs > 0 else None


def _step_stat(info, key):
    """The window's ``gen:step`` spans' values of ``key`` (those that carry
    it)."""
    tr = _spans.of_run(info)
    return [float(s.stats[key])
            for s in (_spans.named(tr, "gen:step") if tr else [])
            if key in s.stats]


def expert_layers(cfg):
    return int(cfg["n_layer"]) - int(cfg["num_dense_layers"])


def moe_experts_hit_per_step(info):
    """Mean over the window's steps of the experts with at least one pick,
    a layer: ``gen:step``'s ``experts_hit`` over the expert layers."""
    hits = _step_stat(info, "experts_hit")
    if not hits or "num_dense_layers" not in info.get("config", {}):
        return None
    return sum(hits) / len(hits) / expert_layers(info["config"])


def decode_step_bytes(cfg, weight_shapes, expert_bytes, pages, page_size,
                      state_bytes, itemsize=2):
    """Bytes one lane step must move: every weight outside the experts
    once (the tied table once: the head reads all of it), the weights of the
    experts at least one lane picked (``expert_bytes``: what the grouped
    kernel fetches; a formulation that streams every expert moves more and
    reads a lower share), the live K/V pages of the step's lanes, and the
    lanes' convolution tails read and written."""
    dense = sum(math.prod(shape) for name, shape in weight_shapes.items()
                if not name.endswith(EXPERT_LEAVES)) * itemsize
    heads, hd = int(cfg["num_key_value_heads"]), \
        int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    attn = sum(1 for t in cfg["layer_types"][:int(cfg["n_layer"])]
               if t == "full_attention")
    kv = pages * page_size * attn * 2 * heads * hd * itemsize
    return dense + expert_bytes + kv + 2 * state_bytes


def decode_bytes_roofline_pct(info):
    """The bytes a lane step must move (:func:`decode_step_bytes`, from the
    step spans' own counts) over the decode program's device time a run
    (``gen_device_ms_per_step``) times the HBM's published rate."""
    cfg = info.get("config", {})
    byts, pages, state = (_step_stat(info, k) for k in
                          ("expert_bytes", "pages", "state_bytes"))
    if not byts or not pages or "num_dense_layers" not in cfg:
        return None
    step_ms = _spans.gen_device_ms_per_step(info)
    if step_ms is None:
        return None
    from perfbench.models import lfm2_moe_lm

    need = decode_step_bytes(
        cfg, lfm2_moe_lm.param_shapes(cfg, int(cfg["n_layer"])),
        statistics.fmean(byts), statistics.fmean(pages),
        int(info["mix"]["page_size"]), statistics.fmean(state or [0.0]))
    rate = peaks.peak(info["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / (1e-3 * step_ms * rate)
