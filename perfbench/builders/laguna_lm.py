"""Program-side construction of the family whose layers attend either to
every earlier token or to a sliding window (``laguna``: poolside Laguna): the
description ``mxnet_tpu.models.HybridLM`` takes (its ``attention`` kind with
a partial, scaled rotation, its ``window`` kind with another head count and a
plain rotation, ``attn_gate``, ``experts`` feed-forward with
``shared_expert_width`` and no selection bias, an untied head), the scoring
symbol and the engine's geometry.  The yardstick (weights, references) lives
elsewhere; this family has no training cell."""

FULL, SLIDING = "full_attention", "sliding_attention"


def _one(values, what):
    if len(set(values)) != 1:
        raise ValueError("the program's block has one %s a layer kind; the "
                         "config states %s" % (what, sorted(set(values))))
    return values[0]


def family_spec(cfg):
    """The program's description of the model (``models.HybridLM``) from a
    ``laguna`` config dict; what the program cannot build is refused here,
    by name."""
    if cfg.get("attention_bias"):
        raise ValueError("the program's block has no bias; the config "
                         "states attention_bias true")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLPs are SiLU-gated; the config "
                         "states hidden_act %r" % cfg["hidden_act"])
    if cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError("the program's experts weight their OUTPUT; the "
                         "config states moe_apply_router_weight_on_input "
                         "true")
    if float(cfg.get("moe_router_logit_softcapping", 0) or 0):
        raise ValueError("the program's router has no soft cap; the config "
                         "states moe_router_logit_softcapping %r"
                         % cfg["moe_router_logit_softcapping"])
    for key in ("n_group", "topk_group"):
        if int(cfg.get(key, 1) or 1) != 1:
            raise ValueError("the program's router has no expert groups; "
                             "the config states %s = %r" % (key, cfg[key]))
    if cfg.get("gating") not in (True, False, "per-head", "per_head"):
        raise ValueError("the program's gate is ONE a head; the config "
                         "states gating %r" % (cfg.get("gating"),))
    if set(cfg.get("gating_types", ())) - {"per_head"}:
        raise ValueError("the program's gate is ONE a head; the config "
                         "states gating_types %s"
                         % sorted(set(cfg["gating_types"])))
    if int(cfg.get("decoder_sparse_step", 1)) != 1:
        raise ValueError("every layer after the dense ones routes; the "
                         "config states decoder_sparse_step %r"
                         % cfg["decoder_sparse_step"])
    layers = int(cfg.get("n_layer", cfg["num_hidden_layers"]))
    types = list(cfg["layer_types"])[:layers]
    if set(types) - {FULL, SLIDING} or FULL not in types:
        raise ValueError("layer kinds the program's block has not, or no "
                         "full layer (the engine's pages): %s"
                         % sorted(set(types)))
    mlps = list(cfg["mlp_layer_types"])[:layers]
    dense = mlps.index("sparse") if "sparse" in mlps else layers
    if set(mlps) - {"dense", "sparse"} or "dense" in mlps[dense:]:
        raise ValueError("the program's dense layers lead and routed ones "
                         "follow; the config states mlp_layer_types %s"
                         % mlps)
    per_layer = [int(h) for h in cfg["num_attention_heads_per_layer"]][:layers]
    heads = {kind: _one([h for h, t in zip(per_layer, types) if t == kind]
                        or [int(cfg["num_attention_heads"])],
                        "count of query heads")
             for kind in (FULL, SLIDING)}
    rope = cfg["rope_parameters"]
    full, sliding = rope[FULL], rope[SLIDING]
    if sliding.get("rope_type", "default") != "default" or \
            float(sliding.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("the program's window kind rotates a whole head by "
                         "plain angles; the config states %r" % (sliding,))
    if full.get("rope_type", "default") not in ("default", "yarn"):
        raise ValueError("the program's rotary op has plain angles and "
                         "YaRN's table; the config states rope_type %r"
                         % full["rope_type"])
    if "truncate" in full and not full["truncate"]:
        raise ValueError("the program's YaRN table rounds its ramp's ends "
                         "outwards; the config states truncate false")
    hd = int(cfg["head_dim"])
    rotated = int(round(float(full.get("partial_rotary_factor", 1)) * hd))
    scaling = None
    if full.get("rope_type") == "yarn":
        scaling = dict(
            factor=float(full["factor"]),
            original_max=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]))
    held = int(cfg["num_experts"])
    return dict(
        family="hybrid_lm", vocab_size=int(cfg["vocab_size"]),
        hidden=int(cfg["hidden_size"]),
        layer_types=["attention" if t == FULL else "window" for t in types],
        num_heads=heads[FULL], window_heads=heads[SLIDING],
        kv_heads=int(cfg["num_key_value_heads"]), head_dim=hd,
        window=int(cfg["sliding_window"]),
        rotary_theta=float(full["rope_theta"]),
        rotary_dim=0 if rotated == hd else rotated, rotary_scaling=scaling,
        window_rotary_theta=float(sliding["rope_theta"]),
        attn_gate=bool(cfg.get("gating")),
        intermediate=int(cfg["intermediate_size"]),
        eps=float(cfg["rms_norm_eps"]),
        num_experts=int(cfg.get("num_experts_published", held)),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        num_dense_layers=dense,
        first_expert=int(cfg.get("first_expert", 0)), experts_held=held,
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scaling=float(cfg["moe_routed_scaling_factor"]),
        router_bias=False,
        shared_expert_width=int(cfg["shared_expert_intermediate_size"]),
        tied_head=bool(cfg["tie_word_embeddings"]),
        dtype=str(cfg.get("weights_dtype", "bfloat16")))


def scoring_symbol(mx, cfg, mix):
    return mx.models.get_hybrid_lm(mx.models.HybridLM(**family_spec(cfg)),
                                   int(mix["max_seq_len"]))


def generator_spec(cfg, mix):
    page = int(mix["page_size"])
    return dict(
        family=family_spec(cfg), max_seq_len=int(mix["max_seq_len"]),
        lane_buckets=tuple(mix["lane_buckets"]), page_size=page,
        # the traffic's most, and the scratch page beside it
        num_pages=int(mix["pool_lanes"]) * int(mix["pool_tokens_per_lane"])
        // page + 1,
        prefill_len_buckets=tuple(mix["prefill_len_buckets"]),
        prefill_batch_buckets=tuple(mix["prefill_batch_buckets"]))
