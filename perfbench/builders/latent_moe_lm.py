"""Program-side construction of the latent-attention family with a shared
expert beside routed experts and sandwich norms (``pangu_ultra_moe``): the
description ``mxnet_tpu.models.HybridLM`` takes (its ``latent`` mixer,
``experts`` feed-forward with ``shared_expert_width`` and no selection bias,
``sandwich_norm``, an untied head), the scoring symbol and the engine's
geometry.  The yardstick (weights, references) lives elsewhere; this family
has no training cell."""


def family_spec(cfg):
    """The program's description of the model (``models.HybridLM``) from a
    ``pangu_ultra_moe`` config dict; what the program cannot build is
    refused here, by name."""
    if cfg.get("rope_scaling") is not None:
        raise ValueError("the program's rotary op has no scaling; the "
                         "config states rope_scaling %r"
                         % (cfg["rope_scaling"],))
    for key in ("n_group", "topk_group"):
        if int(cfg.get(key, 1) or 1) != 1:
            raise ValueError("the program's router has no expert groups; "
                             "the config states %s = %r" % (key, cfg[key]))
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the program's router scores by sigmoid; the "
                         "config states scoring_func %r"
                         % cfg["scoring_func"])
    if cfg.get("use_expert_bias") or cfg.get("topk_method",
                                             "greedy") != "greedy":
        raise ValueError("this family's router picks the top k of the "
                         "scores alone; the config states a selection bias "
                         "(use_expert_bias %r, topk_method %r)"
                         % (cfg.get("use_expert_bias"),
                            cfg.get("topk_method")))
    if int(cfg.get("num_nextn_predict_layers", 0) or 0) and \
            "num_nextn_predict_layers" not in cfg.get("left_out", {}):
        raise ValueError("the program has no multi-token-prediction module; "
                         "the config states num_nextn_predict_layers = %r "
                         "and does not list it under left_out"
                         % cfg["num_nextn_predict_layers"])
    if cfg.get("attention_bias"):
        raise ValueError("the program's block has no bias; the config "
                         "states attention_bias true")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLPs are SiLU-gated; the config "
                         "states hidden_act %r" % cfg["hidden_act"])
    heads = int(cfg["num_attention_heads"])
    if int(cfg.get("num_key_value_heads", heads)) != heads:
        raise ValueError("latent attention has one latent row for all "
                         "heads; the config states num_key_value_heads %r "
                         "of %d" % (cfg["num_key_value_heads"], heads))
    layers = int(cfg.get("n_layer", cfg["num_hidden_layers"]))
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    held = int(cfg["n_routed_experts"])
    return dict(
        family="hybrid_lm", vocab_size=int(cfg["vocab_size"]),
        hidden=int(cfg["hidden_size"]), layer_types=["latent"] * layers,
        num_heads=heads, kv_heads=heads, head_dim=nope + rope,
        nope_dim=nope, rope_dim=rope, v_dim=int(cfg["v_head_dim"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        intermediate=int(cfg["intermediate_size"]),
        eps=float(cfg["rms_norm_eps"]),
        rotary_theta=float(cfg["rope_theta"]),
        num_experts=int(cfg.get("n_routed_experts_published", held)),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        num_dense_layers=int(cfg["first_k_dense_replace"]),
        first_expert=int(cfg.get("first_expert", 0)), experts_held=held,
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        router_bias=False,
        shared_expert_width=int(cfg["n_shared_experts"])
        * int(cfg["moe_intermediate_size"]),
        sandwich_norm=bool(cfg["sandwich_norm"]),
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
