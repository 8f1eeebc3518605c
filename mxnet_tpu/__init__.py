"""mxnet_tpu — a TPU-native deep-learning framework with the API surface of
Apache MXNet 0.9 (reference: /root/reference), built on JAX/XLA.

Import layout mirrors /root/reference/python/mxnet/__init__.py so reference
user scripts port by changing only the import line.
"""
import os as _os
import time as _time

# ``start:import``, the first span of the start-up record (profiler.py):
# stamped here, recorded at the bottom once ``profiler`` can be imported
_t_import = _time.perf_counter()

# JAX's persistent compilation cache, placed by one rule (docs/how_to/
# env_var.md): where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself
# and nothing here sets a directory; otherwise the cache lives at
# <checkout>/.jax_cache — a fixed path, because the path is part of what a
# cache hit depends on.  Off when the platform is forced to the host:
# XLA:CPU cache entries embed the compiling machine's CPU features and can
# SIGILL when reloaded elsewhere.
if ("JAX_COMPILATION_CACHE_DIR" not in _os.environ
        and _os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
        != "cpu"):
    import jax as _jax

    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.realpath(__file__))), ".jax_cache"))

from . import base
from .base import MXNetError
# telemetry must land before the layers it instruments (callback, faults,
# kvstore, comm_engine, module, io, serving) so their module-level lazy
# handles resolve against a fully initialised registry
from . import telemetry
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from .attribute import AttrScope
from .name import NameManager, Prefix
from . import random
from . import random as rnd
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol
from . import symbol as sym
from .symbol import Symbol
# holds the one cache rule, and registers the compile ledger's listener
# before the first program is built
from . import compile_cache
from . import executor
from .executor import Executor
from . import filesystem
from . import io
from . import recordio
from . import initializer
from . import initializer as init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import callback
from . import faults
from . import guardian
from . import kvstore
from . import kvstore as kv
# server-role bootstrap: under DMLC_ROLE=server this serves and exits
# (reference python/mxnet/kvstore_server.py:58 _init_kvstore_server_module)
from . import kvstore_server
from . import comm_engine
# row-sparse values + the sharded-embedding-table plane; already loaded
# (minus its lazy layers) by kvstore_server's row_merge import
from . import sparse
from . import sharding
from . import model
from . import module
from . import module as mod
from . import rnn
from . import operator
from . import parallel
from . import monitor
from . import monitor as mon
from . import visualization
from . import visualization as viz
from . import profiler
from . import image
from . import models
from . import contrib
from .predictor import Predictor, load_exported
from . import serving
from . import generation
from .ops import register_pallas_op, Param
from . import rtc
from . import torch as th
from . import caffe
from . import checkpoint
from . import notebook
from . import log
from . import misc
from . import libinfo
from .libinfo import __version__
from . import executor_manager

profiler.stamp("start:import", _t_import)
