"""Program-side construction of the ResNet v1 family."""
import jax
import jax.numpy as jnp

from perfbench.harness import arith
from perfbench.models import resnet_v1 as family

LABEL = "softmax_label"


def train_layers(cfg):
    return None


def train_symbol(mx, cfg, mix, layers):
    return mx.models.get_resnet(num_classes=int(cfg["num_classes"]),
                                num_layers=int(cfg["num_layers"]),
                                image_shape=tuple(cfg["image_shape"]))


def train_descs(mx, cfg, mix, global_batch):
    return ([mx.io.DataDesc("data",
                            (global_batch,) + tuple(cfg["image_shape"]))],
            [mx.io.DataDesc(LABEL, (global_batch,))])


def make_batch(cfg, mix, key, global_batch):
    k1, k2 = jax.random.split(key)
    images = jax.random.normal(k1, (global_batch,)
                               + tuple(cfg["image_shape"]), jnp.float32)
    labels = jax.random.randint(k2, (global_batch,), 0,
                                int(cfg["num_classes"]), jnp.int32)
    return images, labels.astype(jnp.float32), images, labels


def items_per_batch(mix, global_batch):
    return global_batch


def train_flops_per_item(cfg, mix, layers):
    return arith.image_train_flops(family.forward_macs(cfg))


def program_params(weights):
    params, aux = weights
    return dict(params), dict(aux)
