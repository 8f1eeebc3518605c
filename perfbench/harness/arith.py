"""Model operations, from shapes alone.

A FLOP is one multiply or one add (a multiply-add counts 2), as the chips'
published peaks count them.  Recomputed work is never counted.
"""


def lm_train_flops_per_token(hidden, inner, heads, layers, vocab, seq):
    """Forward + backward operations per trained token of a GPT-2 block
    stack: 6 x (matmul parameters: 4 H^2 + 2 H I a layer, plus the untied
    head V H; the input embedding is a gather) plus causal attention
    (QK^T and PV, half of the s x s square, 2 FLOP a multiply-add, forward
    once and backward twice)."""
    n_matmul = layers * (4 * hidden * hidden + 2 * hidden * inner) \
        + vocab * hidden
    # per token: 2 matmuls x 2 FLOP x (seq / 2 keys on average) x hidden
    attn_fwd = layers * 2 * 2 * (seq / 2.0) * hidden
    return 6.0 * n_matmul + 3.0 * attn_fwd


def image_train_flops(forward_macs):
    """Forward + backward operations per trained image: 2 FLOP a
    multiply-add, backward twice the forward."""
    return 3.0 * 2.0 * forward_macs


def mfu_pct(flops_per_item, items_per_s, chips, peak_flops):
    return 100.0 * flops_per_item * items_per_s / (chips * peak_flops)
