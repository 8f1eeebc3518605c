"""Symbol composition, shape/type inference, JSON round-trip
(reference: tests/python/unittest/test_symbol.py + test_infer_shape.py)."""

import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=10, name="fc1")
    net = sym.Activation(data=net, act_type="relu", name="relu1")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=net, name="softmax")


def test_compose_and_list():
    net = _mlp()
    args = net.list_arguments()
    assert args == ["data", "fc1_weight", "fc1_bias", "fc2_weight",
                    "fc2_bias", "softmax_label"]
    assert net.list_outputs() == ["softmax_output"]


def test_infer_shape():
    net = _mlp()
    arg_shapes, out_shapes, aux_shapes = net.infer_shape(data=(32, 100))
    d = dict(zip(net.list_arguments(), arg_shapes))
    assert d["fc1_weight"] == (10, 100)
    assert d["fc1_bias"] == (10,)
    assert d["fc2_weight"] == (4, 10)
    assert out_shapes == [(32, 4)]


def test_infer_shape_backward_deduction():
    # shape flows backward from fc weight to the input
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=3, name="fc")
    arg_shapes, _, _ = net.infer_shape(fc_weight=(3, 7), fc_bias=(3,),
                                       data=(5, 7))
    d = dict(zip(net.list_arguments(), arg_shapes))
    assert d["data"] == (5, 7)


def test_deep_chain_shape_convergence():
    # deep chains must reach fixed point (not capped at 3)
    net = sym.Variable("data")
    for i in range(10):
        net = sym.FullyConnected(data=net, num_hidden=8, name="fc%d" % i)
    arg_shapes, out_shapes, _ = net.infer_shape(data=(2, 8))
    assert all(s is not None for s in arg_shapes)
    assert out_shapes == [(2, 8)]


def test_infer_type():
    net = _mlp()
    arg_types, out_types, _ = net.infer_type(data=np.float32)
    assert all(t == np.float32 for t in arg_types)


def test_internals_and_getitem():
    net = _mlp()
    internals = net.get_internals()
    names = internals.list_outputs()
    assert "fc1_output" in names
    fc1 = internals["fc1_output"]
    assert fc1.list_arguments()[:1] == ["data"]


def test_group():
    a, b = sym.Variable("a"), sym.Variable("b")
    g = sym.Group([a + b, a * b])
    assert len(g.list_outputs()) == 2


def test_json_roundtrip():
    net = _mlp()
    js = net.tojson()
    net2 = sym.load_json(js)
    assert net2.list_arguments() == net.list_arguments()
    assert net2.list_outputs() == net.list_outputs()
    arg_shapes, out_shapes, _ = net2.infer_shape(data=(8, 20))
    assert out_shapes == [(8, 4)]


def test_json_roundtrip_with_user_attrs():
    # ADVICE medium: user attrs (lr_mult) must survive load_json
    with mx.AttrScope(lr_mult="0.1"):
        data = sym.Variable("data")
        net = sym.FullyConnected(data=data, num_hidden=2, name="fc")
    js = net.tojson()
    net2 = sym.load_json(js)
    assert net2.list_arguments() == net.list_arguments()
    attrs = net2.attr_dict()
    assert attrs.get("fc", {}).get("lr_mult") == "0.1"


def test_save_load_file():
    net = _mlp()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "net.json")
        net.save(path)
        net2 = sym.load(path)
    assert net2.list_arguments() == net.list_arguments()


def test_symbol_arithmetic_composition():
    a, b = sym.Variable("a"), sym.Variable("b")
    s = (a + b) * 2.0 - a / b
    ex = s.bind(mx.cpu(), {"a": mx.nd.array([2.0, 4.0]),
                           "b": mx.nd.array([1.0, 2.0])})
    out = ex.forward()[0].asnumpy()
    np.testing.assert_allclose(out, [4.0, 10.0], rtol=1e-5)


def test_variable_shape_attr():
    v = sym.Variable("x", shape=(3, 4))
    arg_shapes, _, _ = (v * 2.0).infer_shape()
    assert arg_shapes == [(3, 4)]


# A minimal pre-NNVM-era graph (op params live in a separate "param" dict
# of strings; "{input}_lr_mult" multipliers sit on the op node) — inline
# fallback fixture so the legacy-load path has coverage without the
# reference tree.
_LEGACY_JSON = """{
  "nodes": [
    {"op": "null", "param": {}, "name": "data", "inputs": [],
     "backward_source_id": -1},
    {"op": "null", "param": {}, "name": "fc1_weight", "inputs": [],
     "backward_source_id": -1},
    {"op": "null", "param": {}, "name": "fc1_bias", "inputs": [],
     "backward_source_id": -1},
    {"op": "FullyConnected",
     "param": {"no_bias": "False", "num_hidden": "10"},
     "name": "fc1", "inputs": [[0, 0], [1, 0], [2, 0]],
     "backward_source_id": -1,
     "attr": {"ctx_group": "stage1", "weight_lr_mult": "1.2"}},
    {"op": "null", "param": {}, "name": "softmax_label", "inputs": [],
     "backward_source_id": -1},
    {"op": "Softmax", "param": {"grad_scale": "1"}, "name": "softmax",
     "inputs": [[3, 0], [4, 0]], "backward_source_id": -1}
  ],
  "arg_nodes": [0, 1, 2, 4],
  "heads": [[5, 0]]
}"""


def _check_legacy_graph(net, in_dim):
    args = net.list_arguments()
    assert args[0] == "data" and "fc1_weight" in args \
        and "softmax_label" in args
    _, out_shapes, _ = net.infer_shape(data=(4, in_dim))
    assert out_shapes[0] == (4, 10)
    # op-node attrs survive; "{input}_lr_mult" was pushed down onto the
    # variable as __lr_mult__ (legacy_json_util.cc:60-84)
    attrs = net.attr_dict()
    assert attrs.get("fc1", {}).get("ctx_group") == "stage1"
    assert attrs.get("fc1_weight", {}).get("__lr_mult__") == "1.2"
    assert "weight_lr_mult" not in attrs.get("fc1", {})
    # and the loaded graph round-trips through the current format
    assert mx.sym.load_json(net.tojson()).list_arguments() == args


def test_load_legacy_pre_nnvm_json_inline():
    _check_legacy_graph(mx.sym.load_json(_LEGACY_JSON), 20)


def test_load_legacy_pre_nnvm_json_reference_fixture():
    """The reference's own back-compat fixture, when the tree is present
    (tests/python/unittest/save_000800.json)."""
    import os

    import pytest

    path = "/root/reference/tests/python/unittest/save_000800.json"
    if not os.path.exists(path):
        pytest.skip("reference fixture not available")
    with open(path) as f:
        net = mx.sym.load_json(f.read())
    _check_legacy_graph(net, 100)


def test_call_composition():
    """Reference symbol.py:212-230: x(y) / x(data=y) composes inputs."""
    import numpy as np

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                no_bias=True, name="fc")
    pre = mx.sym.Variable("raw") * 3.0
    composed = net(data=pre)
    assert composed.list_arguments() == ["raw", "fc_weight"]
    ex = composed.simple_bind(mx.cpu(), raw=(2, 4), grad_req="null")
    w = np.random.RandomState(0).randn(2, 4).astype("f")
    ex.arg_dict["fc_weight"][:] = w
    x = np.ones((2, 4), "f")
    out = ex.forward(raw=x)[0].asnumpy()
    np.testing.assert_allclose(out, (3 * x) @ w.T, rtol=1e-5)

    # positional maps to list_arguments order; mixing raises
    composed2 = net(pre)
    assert composed2.tojson() == composed.tojson()
    with pytest.raises(TypeError, match="not both"):
        net(pre, data=pre)
    with pytest.raises(TypeError, match="positional inputs"):
        net(pre, pre, pre)
    # unknown names raise (compose contract)
    with pytest.raises(ValueError, match="not free arguments"):
        net(nonexistent=pre)


def test_debug_str():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    s = net.debug_str()
    assert "Variable:data" in s and "Name=fc" in s
    assert "num_hidden=2" in s and "Outputs:" in s
