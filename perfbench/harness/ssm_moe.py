"""Readers of the per-layer metrics of a family that carries a recurrent
state AND routes over experts in one lane program (``granitemoehybrid`` with
experts: ``ops/moe.py``'s scope ``moe_router``, under which both scoring
rules run; ``generation/engine.py``: the ``state_bytes`` / ``expert_bytes`` /
``pages`` / ``lanes`` of a ``gen:step``).  The scopes ``moe_experts``,
``ssm_step`` and ``ssm_scan`` are read by ``harness/moe.py``'s and
``harness/ssm.py``'s readers, this cell's entries over them.  As every
reader: the run's ``info`` in, a number out, or None where the trace holds
nothing for it (an untraced run, a rehearsal on the host, a program without
these scopes and span arguments).

``decode_bytes_roofline_pct`` divides the bytes a lane step must read by the
decode program's WHOLE device time (``gen_device_ms_per_step``), as
``harness/moe.py`` does and for its reason: XLA prefetches weights under
waits that carry no scope.  The byte count (:func:`decode_step_bytes`) is the
benchmark's arithmetic and counts nothing a step could skip: every weight
outside the routed experts once (the tied table once: the head reads all of
it), the weights of the experts held here that a live lane picked (the step
spans' ``expert_bytes``: what ``moe_grouped`` fetches for a lane step's
pairs, one visit an expert), the lanes' state slots read once and written
once (``state_bytes`` twice), and the K and V rows of the lanes' tokens in
the attention layers, from the spans' ``pages`` less one page a lane (a
lane's last page holds at least one token; what a kernel that walks the live
pages reads, where the XLA gather reads more).
"""
import math
import statistics

from perfbench.harness import peaks
from perfbench.harness import spans as _spans
from perfbench.harness.mla import _scope_ms_per_step
from perfbench.harness.moe import EXPERT_LEAVES, _step_stat


def moe_router_ms_per_step(info):
    """Device time under the scope ``moe_router`` inside the runs of the
    lane program, over their count."""
    return _scope_ms_per_step(info, _spans.in_scope("moe_router"))


def decode_step_bytes(cfg, weight_shapes, expert_bytes, state_bytes, tokens,
                      itemsize=2):
    """Bytes one lane step must move (module docstring): ``weight_shapes``
    {leaf: shape} of the model as held, ``expert_bytes`` the hit held
    experts' weights, ``state_bytes`` the step's lanes' slots, ``tokens``
    the lanes' cached tokens."""
    dense = sum(math.prod(shape) for name, shape in weight_shapes.items()
                if not name.endswith(EXPERT_LEAVES)) * itemsize
    hd = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    attn = list(cfg["layer_types"][:int(cfg["n_layer"])]).count("attention")
    kv = tokens * attn * 2 * int(cfg["num_key_value_heads"]) * hd * itemsize
    return dense + expert_bytes + 2 * state_bytes + kv


def decode_bytes_roofline_pct(info):
    """The bytes a lane step must move (:func:`decode_step_bytes`, from the
    step spans' own counts) over the decode program's device time a run
    (``gen_device_ms_per_step``) times the HBM's published rate."""
    cfg = info.get("config", {})
    experts, state, pages, lanes = (_step_stat(info, k) for k in (
        "expert_bytes", "state_bytes", "pages", "lanes"))
    if not experts or not state or not pages or \
            "num_local_experts" not in cfg:
        return None
    step_ms = _spans.gen_device_ms_per_step(info)
    if step_ms is None:
        return None
    from perfbench.models import granite_moe_hybrid_lm as family

    page = int(info["mix"]["page_size"])
    tokens = max(statistics.fmean(pages) - statistics.fmean(lanes), 0.0) \
        * page
    need = decode_step_bytes(
        cfg, family.param_shapes(cfg, int(cfg["n_layer"])),
        statistics.fmean(experts), statistics.fmean(state), tokens)
    rate = peaks.peak(info["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / (1e-3 * step_ms * rate)
