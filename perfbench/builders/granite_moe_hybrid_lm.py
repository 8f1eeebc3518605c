"""Program-side construction of the hybrid state-space / attention family
WITH routed experts (``granitemoehybrid``, ``num_local_experts`` > 0: IBM
Granite 4.0-H Small): the description ``mxnet_tpu.models.HybridLM`` takes
(its ``mamba`` and ``attention`` mixers, ``experts`` feed-forward from layer
0 with ``router_score="softmax"``, no selection bias, and a shared MLP beside
the routed experts of which this chip holds a share), the scoring symbol and
the engine's geometry.  The yardstick (weights, references) lives elsewhere;
this family has no training cell."""


def family_spec(cfg):
    """The program's description of the model (``models.HybridLM``) from a
    ``granitemoehybrid`` config dict with experts; what the program cannot
    build is refused here, by name."""
    held = int(cfg.get("num_local_experts", 0))
    if not held or not int(cfg.get("num_experts_per_tok", 0)):
        raise ValueError("this builder is the family's with routed experts; "
                         "the config states num_local_experts %r, "
                         "num_experts_per_tok %r (builders/hybrid_lm.py "
                         "builds the dense one)"
                         % (cfg.get("num_local_experts"),
                            cfg.get("num_experts_per_tok")))
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the program's hybrid block has no positional "
                         "encoding; the config states %r"
                         % cfg["position_embedding_type"])
    if int(cfg.get("mamba_n_groups", 1)) != 1:
        raise ValueError("the program's state-space layer has one B/C "
                         "group; the config states mamba_n_groups %r"
                         % cfg["mamba_n_groups"])
    if cfg.get("mamba_proj_bias") or cfg.get("attention_bias") or \
            not cfg.get("mamba_conv_bias", True):
        raise ValueError("the program's hybrid block has a convolution "
                         "bias and no projection bias; the config states "
                         "mamba_proj_bias %r, attention_bias %r, "
                         "mamba_conv_bias %r"
                         % (cfg.get("mamba_proj_bias"),
                            cfg.get("attention_bias"),
                            cfg.get("mamba_conv_bias")))
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("this family's head is tied; the config states "
                         "tie_word_embeddings false")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLPs are SiLU-gated; the config "
                         "states hidden_act %r" % cfg["hidden_act"])
    if cfg.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("the program's block norms are RMSNorm; the config "
                         "states normalization_function %r"
                         % cfg["normalization_function"])
    for key in ("n_group", "topk_group"):
        if int(cfg.get(key, 1) or 1) != 1:
            raise ValueError("the program's router has no expert groups; "
                             "the config states %s = %r" % (key, cfg[key]))
    if int(cfg.get("num_dense_layers", 0)):
        raise ValueError("every layer of this family routes; the config "
                         "states num_dense_layers %r"
                         % cfg["num_dense_layers"])
    if not int(cfg.get("shared_intermediate_size", 0)):
        raise ValueError("this family has a shared MLP beside the routed "
                         "experts; the config states "
                         "shared_intermediate_size %r"
                         % cfg.get("shared_intermediate_size"))
    types = list(cfg["layer_types"])[:int(cfg.get("n_layer",
                                                  cfg["num_hidden_layers"]))]
    if set(types) - {"mamba", "attention"}:
        raise ValueError("layer kinds the program's block has not: %s"
                         % sorted(set(types) - {"mamba", "attention"}))
    heads = int(cfg["num_attention_heads"])
    return dict(
        family="hybrid_lm", vocab_size=int(cfg["vocab_size"]),
        hidden=int(cfg["hidden_size"]), layer_types=types,
        num_heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        # the dense MLP's width: no layer of this family has one
        intermediate=int(cfg["shared_intermediate_size"]),
        ssm_heads=int(cfg["mamba_n_heads"]),
        ssm_head_dim=int(cfg["mamba_d_head"]),
        ssm_state=int(cfg["mamba_d_state"]),
        conv_kernel=int(cfg["mamba_d_conv"]),
        chunk=int(cfg["mamba_chunk_size"]), eps=float(cfg["rms_norm_eps"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        num_experts=int(cfg.get("num_local_experts_published", held)),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        expert_width=int(cfg["intermediate_size"]), num_dense_layers=0,
        first_expert=int(cfg.get("first_expert", 0)), experts_held=held,
        router_bias=False, router_score="softmax",
        shared_expert_width=int(cfg["shared_intermediate_size"]),
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
