"""Operator library.  Importing this package registers every op family into
the central registry (`mxnet_tpu.ops.registry`), from which the imperative
(`mx.nd`) and symbolic (`mx.sym`) surfaces are generated.

Families mirror /root/reference/src/operator/ (see SURVEY.md §2.2):
elemwise/broadcast/reduce, matrix, indexing, init, sampling, ordering,
nn layers, sequence, optimizer updates, contrib.
"""
from .registry import Op, OpContext, register, get_op, list_ops, registered_ops
from .param import Param
from .pallas_op import register_pallas_op

from . import elemwise  # noqa: F401
from . import matrix  # noqa: F401
from . import indexing  # noqa: F401
from . import init_ops  # noqa: F401
from . import sample  # noqa: F401
from . import ordering  # noqa: F401
from . import nn  # noqa: F401
from . import sequence  # noqa: F401
from . import rnn_op  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import spatial  # noqa: F401
from . import contrib_ops  # noqa: F401
from . import attention  # noqa: F401
from . import paged  # noqa: F401
from . import ssm  # noqa: F401
from . import moe  # noqa: F401
from . import ctc  # noqa: F401

__all__ = ["Op", "OpContext", "register", "get_op", "list_ops",
           "registered_ops", "Param", "register_pallas_op"]
