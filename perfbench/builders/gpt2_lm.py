"""Program-side construction of the GPT-2 family: the symbols, input
descriptions and engine geometry that ``mxnet_tpu`` needs to run a config.
The yardstick (weights, batches' contents, references) lives elsewhere."""
import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import arith

LABEL = "softmax_label"


def train_layers(cfg):
    return int(cfg.get("n_layer_train", cfg["n_layer"]))


def _program_can_build(cfg):
    if int(cfg["n_inner"]) != 4 * int(cfg["n_embd"]):
        raise ValueError("the program's transformer block has an inner width "
                         "of 4 x hidden; the config states %s x %s"
                         % (cfg["n_inner"], cfg["n_embd"]))


def train_symbol(mx, cfg, mix, layers):
    _program_can_build(cfg)
    return mx.models.get_transformer_lm(
        vocab_size=int(cfg["vocab_size"]), num_layers=layers,
        num_heads=int(cfg["n_head"]), hidden=int(cfg["n_embd"]),
        seq_len=int(mix["seq_len"]), attn_impl="flash")


def train_descs(mx, cfg, mix, global_batch):
    shape = (global_batch, int(mix["seq_len"]))
    # token ids are whole numbers: as int32 they are exempt from the
    # executor's cast of float32 arguments to the compute type
    return ([mx.io.DataDesc("data", shape, dtype=np.int32)],
            [mx.io.DataDesc(LABEL, shape)])


def make_batch(cfg, mix, key, global_batch):
    """One seeded batch on the device: (program data, program label,
    reference inputs, reference labels).  Every row differs."""
    seq = int(mix["seq_len"])
    ids = jax.random.randint(key, (global_batch, seq + 1), 0,
                             int(cfg["vocab_size"]), jnp.int32)
    tokens, labels = ids[:, :-1], ids[:, 1:]
    return tokens, labels.astype(jnp.float32), tokens, labels


def items_per_batch(mix, global_batch):
    return global_batch * int(mix["seq_len"])


def train_flops_per_item(cfg, mix, layers):
    return arith.lm_train_flops_per_token(
        int(cfg["n_embd"]), int(cfg["n_inner"]), int(cfg["n_head"]), layers,
        int(cfg["vocab_size"]), int(mix["seq_len"]))


def program_params(weights):
    """(arg params, aux params) under the program's parameter names, which
    are the family's own."""
    return dict(weights), {}


# -- serving ---------------------------------------------------------------

def scoring_symbol(mx, cfg, mix):
    _program_can_build(cfg)
    return mx.models.get_transformer_lm(
        vocab_size=int(cfg["vocab_size"]), num_layers=int(cfg["n_layer"]),
        num_heads=int(cfg["n_head"]), hidden=int(cfg["n_embd"]),
        seq_len=int(mix["max_seq_len"]), attn_impl="flash")


def generator_spec(cfg, mix):
    page = int(mix["page_size"])
    return dict(
        vocab_size=int(cfg["vocab_size"]), num_layers=int(cfg["n_layer"]),
        num_heads=int(cfg["n_head"]), hidden=int(cfg["n_embd"]),
        max_seq_len=int(mix["max_seq_len"]),
        lane_buckets=tuple(mix["lane_buckets"]), page_size=page,
        num_pages=int(mix["pool_lanes"]) * int(mix["pool_tokens_per_lane"])
        // page,
        prefill_len_buckets=tuple(mix["prefill_len_buckets"]),
        prefill_batch_buckets=tuple(mix["prefill_batch_buckets"]))
