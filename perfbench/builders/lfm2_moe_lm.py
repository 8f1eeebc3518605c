"""Program-side construction of the short-convolution / attention family
with routed experts (``lfm2_moe``): the description ``mxnet_tpu.models.
HybridLM`` takes (its ``conv`` mixer, rotary attention with QK-norm and
``experts`` feed-forward), the scoring symbol and the engine's geometry.  The
yardstick (weights, references) lives elsewhere; this family has no
training cell."""


def family_spec(cfg):
    """The program's description of the model (``models.HybridLM``) from an
    ``lfm2_moe`` config dict; what the program cannot build is refused
    here, by name."""
    for key in ("num_shared_experts", "n_shared_experts"):
        if int(cfg.get(key, 0) or 0):
            raise ValueError("the program's expert layer has no shared "
                             "expert; the config states %s = %r"
                             % (key, cfg[key]))
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the program's router scores by sigmoid and picks "
                         "the top k of score + bias; the config states "
                         "scoring_func %r" % cfg["scoring_func"])
    if not cfg.get("use_expert_bias", True):
        raise ValueError("the program's router always takes a selection "
                         "bias; the config states use_expert_bias false")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("the program's rotary op has no scaling; the "
                         "config states rope_scaling %r"
                         % (cfg["rope_scaling"],))
    for key in ("conv_bias", "attention_bias", "mlp_bias"):
        if cfg.get(key):
            raise ValueError("the program's block has no bias; the config "
                             "states %s true" % key)
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the program's block has a tied head")
    kinds = {"conv": "conv", "full_attention": "attention"}
    types = list(cfg["layer_types"])[:int(cfg.get("n_layer",
                                                  cfg["num_hidden_layers"]))]
    if set(types) - set(kinds):
        raise ValueError("layer kinds the program's block has not: %s"
                         % sorted(set(types) - set(kinds)))
    heads, experts = int(cfg["num_attention_heads"]), int(cfg["num_experts"])
    first = int(cfg.get("first_expert", 0))
    return dict(
        family="hybrid_lm", vocab_size=int(cfg["vocab_size"]),
        hidden=int(cfg["hidden_size"]),
        layer_types=[kinds[t] for t in types], num_heads=heads,
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        intermediate=int(cfg["intermediate_size"]),
        conv_kernel=int(cfg["conv_L_cache"]), eps=float(cfg["norm_eps"]),
        rotary_theta=float(cfg["rope_theta"]), qk_norm=True,
        num_experts=experts,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        num_dense_layers=int(cfg["num_dense_layers"]), first_expert=first,
        experts_held=int(cfg.get("experts_held", experts - first)),
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
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
