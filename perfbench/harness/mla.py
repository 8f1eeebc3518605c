"""Readers of the latent-attention family's per-layer metrics
(``ops/paged.py``: scopes ``latent_attention`` and ``paged_attention_latent``;
``models/hybrid_lm.py``: the shared expert's three ops, which the executor
traces under their nodes' names ``layer<i>_shared_in`` / ``_gate`` / ``_out``;
``generation/engine.py``: the ``latent_bytes`` / ``experts_hit`` /
``expert_bytes`` of a ``gen:step``).  The scope ``moe_experts`` is read by
``harness/moe.py``'s reader, this cell's entry over it.  As
every reader: the run's ``info`` in, a number out, or None where the trace
holds nothing for it (an untraced run, a rehearsal on the host, a program
without these scopes and span arguments).

``decode_bytes_roofline_pct`` divides the bytes a lane step must read by the
decode program's WHOLE device time (``gen_device_ms_per_step``), as
``harness/moe.py`` does and for its reason: XLA prefetches weights under
waits that carry no scope.  The byte count (:func:`decode_step_bytes`) is
the benchmark's arithmetic: every weight outside the routed experts once
EXCEPT the embedding table (the head is untied: a step reads one row a lane
of the table, not the table), the HIT experts' weights from the step spans'
``expert_bytes`` (the experts held here that a lane picked: what
``moe_grouped`` fetches for a lane step's pairs, one visit an expert), and
the live latent rows from ``latent_bytes`` (what a
kernel that walks the live pages would read; the XLA gather reads the whole
table and writes it again, so this share reads low while the gather stands,
never high).
"""
import math
import statistics

from perfbench.harness import peaks
from perfbench.harness import spans as _spans
from perfbench.harness.moe import EXPERT_LEAVES, _step_stat

EMBEDDING = "tok_embed_weight"
# the shared expert's products and gate, by their nodes' names
SHARED_EXPERT = r"layer\d+_shared_(?:in|gate|out)"


def _scope_ms_per_step(info, match):
    """Device time of the operations ``match`` picks inside the decode
    program's runs, over their count (a prefill's time is not a step's)."""
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
            if j < len(spans) and spans[j][0] <= op.start and match(op):
                secs += op.end - op.start
    return 1e3 * secs / n if n and secs > 0 else None


def mla_attn_ms_per_step(info):
    return _scope_ms_per_step(info,
                              _spans.in_scope("paged_attention_latent"))


def moe_shared_ms_per_step(info):
    return _scope_ms_per_step(info, _spans.in_scope(SHARED_EXPERT))


def mla_latent_gb_per_step(info):
    """Mean over the window's steps of ``latent_bytes``: the latent rows of
    the step's live tokens, over every latent layer."""
    byts = _step_stat(info, "latent_bytes")
    return statistics.fmean(byts) / 1e9 if byts else None


def expert_layers(cfg):
    return int(cfg["n_layer"]) - int(cfg["first_k_dense_replace"])


def moe_experts_hit_per_step(info):
    """Mean over the window's steps of the experts held here with at least
    one pick, a layer: ``gen:step``'s ``experts_hit`` over the expert
    layers."""
    hits = _step_stat(info, "experts_hit")
    if not hits or "first_k_dense_replace" not in info.get("config", {}):
        return None
    return statistics.fmean(hits) / expert_layers(info["config"])


def decode_step_bytes(weight_shapes, lanes, expert_bytes, latent_bytes,
                      itemsize=2):
    """Bytes one lane step must read (module docstring): ``weight_shapes``
    {leaf: shape} of the model as held, ``lanes`` the rows of the embedding a
    step reads, ``expert_bytes`` the hit held experts' weights,
    ``latent_bytes`` the live latent rows."""
    dense = sum(math.prod(shape) for name, shape in weight_shapes.items()
                if not name.endswith(EXPERT_LEAVES) and name != EMBEDDING)
    rows = lanes * weight_shapes[EMBEDDING][1]
    return (dense + rows) * itemsize + expert_bytes + latent_bytes


def decode_bytes_roofline_pct(info):
    """The bytes a lane step must read (:func:`decode_step_bytes`, from the
    step spans' own counts) over the decode program's device time a run
    (``gen_device_ms_per_step``) times the HBM's published rate."""
    cfg = info.get("config", {})
    latent, experts = (_step_stat(info, k) for k in
                       ("latent_bytes", "expert_bytes"))
    if not latent or not experts or "first_k_dense_replace" not in cfg:
        return None
    step_ms = _spans.gen_device_ms_per_step(info)
    if step_ms is None:
        return None
    from perfbench.models import latent_moe_lm

    need = decode_step_bytes(
        latent_moe_lm.param_shapes(cfg, int(cfg["n_layer"])),
        max(int(b) for b in info["mix"]["lane_buckets"]),
        statistics.fmean(experts), statistics.fmean(latent))
    rate = peaks.peak(info["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / (1e-3 * step_ms * rate)
