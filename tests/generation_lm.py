"""What the generation test files share: the tiny transformer LM (vocab 64,
2 layers, deterministic random weights, so greedy argmax transcripts are
stable references), the engine spec they build it with, and the sequential
reference continuous batching must reproduce."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.generation import DecodeEngine

V, LAYERS, HEADS, HID, S = 64, 2, 2, 32, 32

SPEC = dict(vocab_size=V, num_layers=LAYERS, num_heads=HEADS, hidden=HID,
            max_seq_len=S, lane_buckets=(1, 2, 4), page_size=4,
            num_pages=48, prefill_len_buckets=(8, 16, 32))


def _lm_params(seed=0):
    net = mx.models.get_transformer_lm(vocab_size=V, num_layers=LAYERS,
                                       num_heads=HEADS, hidden=HID,
                                       seq_len=S)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(seed)
    params = {
        name: mx.nd.array(rng.randn(*shp).astype(np.float32) * 0.05)
        for name, shp in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    return net, params


_NET, _PARAMS = _lm_params()


def _prompts(rng, n, lo=2, hi=12):
    return [[int(t) for t in rng.randint(0, V, size=rng.randint(lo, hi))]
            for _ in range(n)]


def _sequential_reference(params, workload, **spec_overrides):
    """One request at a time through a fresh engine: the ground truth
    continuous batching must reproduce bit-identically."""
    spec = dict(SPEC, **spec_overrides)
    eng = DecodeEngine(params, **spec)
    try:
        return [eng.generate(p, n) for p, n in workload]
    finally:
        eng.stop()
