"""Readers of the per-layer metrics of a family whose layers attend either
to every earlier token, over pages, or to a sliding window, over a ring a
lane (``laguna``: ``ops/paged.py``'s scopes ``window_attention`` and
``paged_attention``; ``generation/engine.py``: the ``window_bytes`` /
``pages`` / ``lanes`` / ``experts_hit`` / ``expert_bytes`` of a
``gen:step``).  The scope ``moe_experts`` is read by ``harness/moe.py``'s
reader, this cell's entry over it.  As every reader: the run's ``info`` in, a
number out, or None where the trace holds nothing for it (an untraced run, a
rehearsal on the host, a program without these scopes and span arguments).

The arithmetic, all of it from the configuration's own keys:

``ring_bytes_per_lane``  a sliding layer's K and V rings, ``sliding_window``
    rows of ``num_key_value_heads x head_dim`` values each, over the sliding
    layers: what a lane step FETCHES of a live lane whatever its length
    (``gen:step``'s ``window_bytes`` is lanes times this).
``paged_token_bytes``  K and V of one token over the layers of one kind, as
    pages would hold them.
``window_read_share_pct``  ``window_bytes`` over what the same lanes' live
    pages (``pages`` x ``page_size`` tokens) would be in the sliding layers
    had they been paged: 100 where the ring is not engaged.
``window_attn_roofline_pct``  the rings fetched and this step's rows written
    (``window_step_bytes``) over the scope's device time x the HBM's rate.
    Counted as fetched, not as useful: a lane 100 tokens deep still moves
    its whole ring.
``decode_bytes_roofline_pct``  the bytes a lane step must move
    (:func:`decode_step_bytes`) over the decode program's WHOLE device time
    (``gen_device_ms_per_step``), as ``harness/moe.py`` does and for its
    reason: XLA prefetches weights under waits that carry no scope.
"""
import math
import statistics

from perfbench.harness import peaks
from perfbench.harness import spans as _spans
from perfbench.harness.mla import EMBEDDING, _scope_ms_per_step
from perfbench.harness.moe import EXPERT_LEAVES, _step_stat

FULL, SLIDING = "full_attention", "sliding_attention"


def _layers(cfg, kind):
    return list(cfg["layer_types"][:int(cfg["n_layer"])]).count(kind)


def _row_bytes(cfg, itemsize=2):
    """K and V of one token in one layer."""
    return 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) \
        * itemsize


def ring_bytes_per_lane(cfg, itemsize=2):
    return _layers(cfg, SLIDING) * int(cfg["sliding_window"]) \
        * _row_bytes(cfg, itemsize)


def paged_token_bytes(cfg, kind, itemsize=2):
    return _layers(cfg, kind) * _row_bytes(cfg, itemsize)


def window_step_bytes(cfg, window_bytes, lanes, itemsize=2):
    """What the sliding layers of one lane step move: the live lanes' rings
    read, one row of each written."""
    return window_bytes + lanes * _layers(cfg, SLIDING) \
        * _row_bytes(cfg, itemsize)


def _is_laguna(info):
    return "sliding_window" in info.get("config", {})


def window_attn_ms_per_step(info):
    """Device time under ``window_attention`` inside the runs of the lane
    program, over their count."""
    return _scope_ms_per_step(info, _spans.in_scope("window_attention"))


def full_attn_ms_per_step(info):
    """Device time under ``paged_attention`` (the full layers' attention
    over pages) inside the runs of the lane program, over their count."""
    if not _is_laguna(info):
        return None
    return _scope_ms_per_step(info, _spans.in_scope("paged_attention"))


def window_gb_per_step(info):
    """Mean over the window's steps of ``window_bytes``: the rings of the
    step's live lanes, over every sliding layer."""
    byts = _step_stat(info, "window_bytes")
    return statistics.fmean(byts) / 1e9 if byts else None


def window_read_share_pct(info):
    rings, pages = (_step_stat(info, k) for k in ("window_bytes", "pages"))
    if not rings or not pages or not _is_laguna(info):
        return None
    as_pages = statistics.fmean(pages) * int(info["mix"]["page_size"]) \
        * paged_token_bytes(info["config"], SLIDING)
    return 100.0 * statistics.fmean(rings) / as_pages if as_pages else None


def window_attn_roofline_pct(info):
    rings, lanes = (_step_stat(info, k) for k in ("window_bytes", "lanes"))
    ms = window_attn_ms_per_step(info)
    if not rings or not lanes or ms is None or not _is_laguna(info):
        return None
    need = window_step_bytes(info["config"], statistics.fmean(rings),
                             statistics.fmean(lanes))
    rate = peaks.peak(info["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / (1e-3 * ms * rate)


def expert_layers(cfg):
    return list(cfg["mlp_layer_types"][:int(cfg["n_layer"])]).count("sparse")


def moe_experts_hit_per_step(info):
    """Mean over the window's steps of the experts held here with at least
    one pick, a layer: ``gen:step``'s ``experts_hit`` over the sparse
    layers."""
    hits = _step_stat(info, "experts_hit")
    if not hits or "mlp_layer_types" not in info.get("config", {}):
        return None
    return statistics.fmean(hits) / expert_layers(info["config"])


def decode_step_bytes(cfg, weight_shapes, lanes, expert_bytes, tokens,
                      window_bytes, itemsize=2):
    """Bytes one lane step must move: every weight outside the routed
    experts once EXCEPT the embedding table (the head is untied: a step reads
    one row a lane of the table), the HIT held experts' weights
    (``expert_bytes``: what ``moe_grouped`` fetches for a lane step's pairs),
    K and V of the lanes' ``tokens`` in the full layers (their live pages),
    and the sliding layers' rings read and one row of each written
    (:func:`window_step_bytes`)."""
    dense = sum(math.prod(shape) for name, shape in weight_shapes.items()
                if not name.endswith(EXPERT_LEAVES) and name != EMBEDDING)
    rows = lanes * weight_shapes[EMBEDDING][1]
    return (dense + rows) * itemsize + expert_bytes \
        + tokens * paged_token_bytes(cfg, FULL, itemsize) \
        + window_step_bytes(cfg, window_bytes, lanes, itemsize)


def decode_bytes_roofline_pct(info):
    """The bytes a lane step must move (:func:`decode_step_bytes`, from the
    step spans' own counts) over the decode program's device time a run
    (``gen_device_ms_per_step``) times the HBM's published rate."""
    cfg = info.get("config", {})
    experts, rings, pages, lanes = (_step_stat(info, k) for k in (
        "expert_bytes", "window_bytes", "pages", "lanes"))
    if not experts or not rings or not pages or not _is_laguna(info):
        return None
    step_ms = _spans.gen_device_ms_per_step(info)
    if step_ms is None:
        return None
    from perfbench.models import laguna_lm

    need = decode_step_bytes(
        cfg, laguna_lm.param_shapes(cfg, int(cfg["n_layer"])),
        statistics.fmean(lanes), statistics.fmean(experts),
        statistics.fmean(pages) * int(info["mix"]["page_size"]),
        statistics.fmean(rings))
    rate = peaks.peak(info["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / (1e-3 * step_ms * rate)
