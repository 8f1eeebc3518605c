"""Transformer LM model family: builder shapes, Module training through
the Pallas flash-attention op, LayerNorm/gelu op parity."""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_layer_norm_matches_numpy():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 6, 8).astype(np.float32)
    g = rng.rand(8).astype(np.float32) + 0.5
    b = rng.randn(8).astype(np.float32)
    out = mx.nd.LayerNorm(mx.nd.array(x), mx.nd.array(g), mx.nd.array(b),
                          eps=1e-5)
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mean) / np.sqrt(var + 1e-5) * g + b
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-4, atol=1e-5)


def test_layer_norm_gradient():
    from mxnet_tpu.test_utils import check_numeric_gradient

    net = mx.sym.LayerNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"),
                           mx.sym.Variable("beta"))
    rng = np.random.RandomState(1)
    check_numeric_gradient(
        net, {"data": rng.randn(3, 7).astype(np.float32),
              "gamma": rng.rand(7).astype(np.float32) + 0.5,
              "beta": rng.randn(7).astype(np.float32)},
        numeric_eps=1e-3, rtol=1e-2, atol=1e-2)


def test_layer_norm_output_mean_var():
    rng = np.random.RandomState(2)
    x = rng.randn(3, 5).astype(np.float32)
    net = mx.sym.LayerNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"),
                           mx.sym.Variable("beta"), output_mean_var=True)
    assert len(net.list_outputs()) == 3
    ex = net.bind(mx.cpu(), {"data": mx.nd.array(x),
                             "gamma": mx.nd.ones((5,)),
                             "beta": mx.nd.zeros((5,))})
    ex.forward(is_train=False)
    out, mean, std = (o.asnumpy() for o in ex.outputs)
    np.testing.assert_allclose(mean, x.mean(-1), rtol=1e-5, atol=1e-6)
    # upstream's third output is the standard deviation (out, mean, std)
    np.testing.assert_allclose(std, np.sqrt(x.var(-1) + 1e-5),
                               rtol=1e-4, atol=1e-5)


def test_gelu_erf_ops():
    x = np.linspace(-3, 3, 13).astype(np.float32)
    g = mx.nd.gelu(mx.nd.array(x)).asnumpy()
    from scipy.special import erf as sp_erf
    ref = 0.5 * x * (1 + sp_erf(x / np.sqrt(2)))
    np.testing.assert_allclose(g, ref, rtol=1e-3, atol=1e-4)
    e = mx.nd.erf(mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(e, sp_erf(x), rtol=1e-5, atol=1e-6)


def test_transformer_shapes():
    net = mx.models.get_transformer_lm(vocab_size=100, num_layers=2,
                                       num_heads=4, hidden=64, seq_len=16)
    arg_shapes, out_shapes, _ = net.infer_shape(data=(8, 16),
                                                softmax_label=(8, 16))
    assert out_shapes[0] == (8 * 16, 100)
    names = net.list_arguments()
    assert "pos_embed_weight" in names and "tok_embed_weight" in names


def test_transformer_lm_learns_next_token():
    """End-to-end: Module.fit on a deterministic next-token task reaches
    ~perfect accuracy — exercises Embedding/LayerNorm/gelu/flash-attention
    fwd+bwd through the fused step."""
    V, S, B = 50, 32, 4
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=2,
                                       num_heads=4, hidden=64, seq_len=S)
    rng = np.random.RandomState(0)
    X = rng.randint(0, V, size=(64, S)).astype(np.float32)
    Y = (X + 1) % V
    it = mx.io.NDArrayIter(X, Y, batch_size=B, label_name="softmax_label")
    mod = mx.mod.Module(net, label_names=("softmax_label",))
    mod.fit(it, num_epoch=6, optimizer="adam",
            optimizer_params={"learning_rate": 1e-2})
    it.reset()
    correct = total = 0
    for batch in it:
        mod.forward(batch, is_train=False)
        out = mod.get_outputs()[0].asnumpy()
        lab = batch.label[0].asnumpy().reshape(-1)
        correct += (out.argmax(-1) == lab).sum()
        total += lab.size
    assert correct / total > 0.9, correct / total


# -- the four graphs of models/transformer.py ------------------------------

_TOY = dict(vocab_size=97, num_layers=2, num_heads=4, hidden=32)
_MAX_SEQ, _PAGE = 32, 4


def _graph(which):
    """One of the four builders at the toy size, under a fresh NameManager
    (as generation/engine.py builds them): the unnamed Reshapes' names then
    do not depend on what the process built before."""
    from mxnet_tpu.models import transformer
    from mxnet_tpu.name import NameManager

    with NameManager():
        if which == "train":
            return transformer.get_transformer_lm(seq_len=_MAX_SEQ, **_TOY)
        if which == "prefill":
            return transformer.get_transformer_lm_prefill(
                seq_len=16, max_seq_len=_MAX_SEQ, **_TOY)
        build = {"decode": transformer.get_transformer_lm_decode,
                 "catchup": transformer.get_transformer_lm_catchup}[which]
        return build(max_seq_len=_MAX_SEQ, page_size=_PAGE, **_TOY)


# sha256 of tojson() as PR 29's five written-out blocks emitted it (decode:
# as PR 31 left it, which gave it ``source`` / ``prev_ids`` / ``next_ids``).
# The JSON is the compile-cache fingerprint and fixes every named scope of
# the device trace; a PR that changes a graph on purpose replaces its
# digest here and says so in CHANGES.md.
_DIGESTS = {
    "train":
        "a4618d6fec48840218f0e34710c4d4b30ab7388996d44dbb8388ac5ed0856b7a",
    "prefill":
        "dc31b9bc296ecc03ad611e158aeae9415ffaaea63ea1767bcad9a13b9dec1029",
    "decode":
        "2d8d06418b4f29a1f216b158f089dfcd909aba9917db7bd9eb0c580bab4a06f7",
    "catchup":
        "bbcb236c75de3f6db21d6872d6bf65cea23a63419e028eae1db30707cbd174f1",
}


@pytest.mark.parametrize("which", sorted(_DIGESTS))
def test_graph_json_is_pinned(which):
    import hashlib

    digest = hashlib.sha256(_graph(which).tojson().encode()).hexdigest()
    assert digest == _DIGESTS[which]


@pytest.mark.parametrize("which,feeds", [
    ("prefill", {"data": (2, 16)}),
    ("decode", {"data": (3,), "positions": (3,), "page_table": (3, 8),
                "source": (3,), "prev_ids": (3,)}),
    ("catchup", {"data": (3, 5), "positions": (3, 5),
                 "page_table": (3, 8)}),
])
def test_serving_graphs_bind_the_training_checkpoint(which, feeds):
    """Less its inputs and KV planes, every serving graph takes the training
    graph's parameters: the same names with the same inferred shapes."""
    from mxnet_tpu.models.transformer import lane_plane_names

    def params(net, feeds):
        shapes, _, _ = net.infer_shape(**feeds)
        return {name: shape
                for name, shape in zip(net.list_arguments(), shapes)
                if name not in feeds}

    train = params(_graph("train"), {"data": (2, _MAX_SEQ),
                                     "softmax_label": (2, _MAX_SEQ)})
    if which != "prefill":
        heads = _TOY["num_heads"]
        plane = (6, _PAGE, heads, _TOY["hidden"] // heads)
        feeds = dict(feeds, **{name: plane for name in
                               lane_plane_names(_TOY["num_layers"])})
    assert params(_graph(which), feeds) == train
    assert len(train) == 6 + 12 * _TOY["num_layers"]


def test_flash_is_the_one_training_attention():
    """The A/B alternative went with PR 30: the keyword that perfbench still
    passes accepts "flash" alone, and the op is not registered."""
    with pytest.raises(ValueError):
        mx.models.get_transformer_lm(attn_impl="splash", **_TOY)
    assert not hasattr(mx.sym, "_contrib_SplashAttention")
    assert not hasattr(mx.nd, "_contrib_SplashAttention")
