"""Model zoo: symbol builders for the benchmark configs
(reference: example/image-classification/symbols/*.py — capability parity,
fresh TPU-oriented implementations; NCHW layout with bf16-friendly blocks)."""

import numpy as np

from .lenet import get_lenet
from .mlp import get_mlp
from .resnet import get_resnet
from .alexnet import get_alexnet
from .inception_bn import get_inception_bn
from .inception_v3 import get_inception_v3
from .vgg import get_vgg
from .googlenet import get_googlenet
from .ssd import get_ssd_train, get_ssd_detect, get_ssd_symbols
from .transformer import get_transformer_lm, TransformerLMFamily
from .hybrid_lm import get_hybrid_lm, HybridLM
from .dlrm import get_dlrm


def lm_family(spec):
    """The LM family object a ``spec()`` dict describes (its ``family``
    key names the class): what ``DecodeEngine(family=...)`` takes."""
    families = {cls.name: cls for cls in (TransformerLMFamily, HybridLM)}
    spec = dict(spec)
    kind = spec.pop("family", None)
    if kind not in families:
        raise ValueError("family must be one of %s, got %r"
                         % (sorted(families), kind))
    return families[kind](**spec)


def generator_family(family=None, vocab_size=0, num_layers=4, num_heads=8,
                     hidden=512, dtype="float32", **_):
    """The family of a generator spec (``DecodeEngine``'s keywords, the
    rest ignored): its ``family``, an object or a ``spec()`` dict, else the
    default family of its width keys, ``dtype`` its K/V planes'."""
    if family is None:
        return TransformerLMFamily(vocab_size, num_layers, num_heads, hidden,
                                   dtype=np.dtype(dtype).name)
    return lm_family(family) if isinstance(family, dict) else family


__all__ = ["get_hybrid_lm", "HybridLM", "TransformerLMFamily", "lm_family",
           "generator_family",
           "get_ssd_train", "get_ssd_detect", "get_ssd_symbols",
           "get_lenet", "get_mlp", "get_resnet", "get_alexnet",
           "get_inception_bn", "get_inception_v3", "get_vgg",
           "get_googlenet", "get_transformer_lm", "get_dlrm"]
