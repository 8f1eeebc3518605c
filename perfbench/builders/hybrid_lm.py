"""Program-side construction of the hybrid state-space / attention family:
the description ``mxnet_tpu.models.HybridLM`` takes, the scoring symbol and
the engine's geometry.  The yardstick (weights, references) lives elsewhere;
this family has no training cell."""


def family_spec(cfg):
    """The program's description of the model (``models.HybridLM``) from a
    ``granitemoehybrid`` config dict; what the program cannot build is
    refused here, by name."""
    if int(cfg.get("num_local_experts", 0)) or \
            int(cfg.get("num_experts_per_tok", 0)):
        raise ValueError("the program's hybrid block has no expert layer")
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the program's hybrid block has no positional "
                         "encoding; the config states %r"
                         % cfg["position_embedding_type"])
    if int(cfg.get("mamba_n_groups", 1)) != 1 or cfg.get("mamba_proj_bias") \
            or cfg.get("attention_bias") or \
            not cfg.get("mamba_conv_bias", True) or \
            not cfg.get("tie_word_embeddings", True):
        raise ValueError("the program's hybrid block has one B/C group, a "
                         "convolution bias, no projection bias and a tied "
                         "head")
    if int(cfg["shared_intermediate_size"]) != int(cfg["intermediate_size"]):
        raise ValueError("one dense gated MLP of intermediate_size only")
    heads = int(cfg["num_attention_heads"])
    return dict(
        family="hybrid_lm", vocab_size=int(cfg["vocab_size"]),
        hidden=int(cfg["hidden_size"]), layer_types=list(cfg["layer_types"]),
        num_heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        intermediate=int(cfg["intermediate_size"]),
        ssm_heads=int(cfg["mamba_n_heads"]),
        ssm_head_dim=int(cfg["mamba_d_head"]),
        ssm_state=int(cfg["mamba_d_state"]),
        conv_kernel=int(cfg["mamba_d_conv"]),
        chunk=int(cfg["mamba_chunk_size"]), eps=float(cfg["rms_norm_eps"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
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
