"""Model-level helpers: checkpointing and the kvstore update paths.

Parity: /root/reference/python/mxnet/model.py (BatchEndParam :25,
_create_kvstore :40-77, _update_params[_on_kvstore] :88-116,
save_checkpoint :319, load_checkpoint :349).  The legacy FeedForward API is
provided for porting convenience and delegates to Module.
"""
from __future__ import annotations

import collections
import logging
import os
from typing import Dict, Optional, Tuple

from . import ndarray as nd
from . import symbol as sym
from .base import MXNetError
from . import kvstore as kvs

__all__ = ["BatchEndParam", "save_checkpoint", "load_checkpoint",
           "load_checkpoint_state", "FeedForward"]

BatchEndParam = collections.namedtuple(
    "BatchEndParams", ["epoch", "nbatch", "eval_metric", "locals"])


def _create_kvstore(kvstore, num_device, arg_params):
    """Resolve a kvstore spec to (kv, update_on_kvstore) (reference
    model.py:40-77)."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            # a single in-step device group needs no kvstore round-trip
            kv = None
        else:
            kv = kvs.create(kvstore)
            if "dist" not in kvstore:
                # one mesh executor spans the devices, so gradients leave
                # the compiled step already summed (psum) whatever their
                # size.  The reference's 16M-element rule chose where N
                # per-device copies were merged; here it only pushed
                # every realistic model (one parameter above 16M
                # elements) off the fused step onto the eager per-key loop
                update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return kv, update_on_kvstore


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore, skip_indices=()):
    """``skip_indices``: params routed elsewhere (row_sparse slots ride
    the sparse plane's sharded tables — initializing them here would ship
    a dense copy of a table that must never leave the servers)."""
    skip = frozenset(skip_indices)
    for idx, param_on_devs in enumerate(param_arrays):
        if idx in skip:
            continue
        kvstore.init(idx, arg_params[param_names[idx]])
        if update_on_kvstore:
            kvstore.pull(idx, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore,
                              param_order=None, defer_wait=False):
    """Centralized update: push grads, pull weights (reference model.py:88).

    All pushes are issued FIRST, in ``param_order`` (backward order — the
    order gradients become available), each with ``priority=-index`` so an
    async kvstore services front-layer keys first; pulls follow in forward
    order.  On an async store nothing here blocks — with ``defer_wait``
    the caller overlaps communication with the next batch's host-side
    prep and waits later (Module._wait_async_comm); otherwise a final
    ``wait_all`` restores the synchronous contract.  On a plain kvstore
    push/pull complete inline and ``wait`` is the no-op base method, so
    behavior is unchanged."""
    n = len(param_arrays)
    if param_order is None:
        param_order = range(n - 1, -1, -1)

    def has_grad(index):
        g = grad_arrays[index]
        return not (g is None or (isinstance(g, list) and g[0] is None))

    for index in param_order:
        if has_grad(index):
            kvstore.push(index, grad_arrays[index], priority=-index)
    for index in range(n):
        if has_grad(index):
            kvstore.pull(index, param_arrays[index], priority=-index)
    if not defer_wait:
        kvstore.wait_all()


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None):
    """Replicated-updater path (reference model.py:99-116)."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list is None or (isinstance(grad_list, list) and
                                 grad_list[0] is None):
            continue
        if not isinstance(arg_list, list):
            arg_list, grad_list = [arg_list], [grad_list]
        if kvstore:
            kvstore.push(index, grad_list, priority=-index)
            kvstore.pull(index, grad_list, priority=-index)
            # async store: the pulled-back grads feed the local updater
            # next — wait this key out before reading
            kvstore.wait(index)
        for k, p in enumerate(zip(arg_list, grad_list)):
            w, g = p
            updater(index * num_device + k, g, w)


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params,
                    max_to_keep=None, extra_state=None,
                    mark_last_good=False):
    """Write prefix-symbol.json + prefix-%04d.params (reference
    model.py:319-349; format per ndarray.cc:633-714).

    Both files land atomically (tmp + fsync + ``os.replace``) and the
    params file carries a CRC32 sidecar, so a crash mid-save can neither
    tear the newest checkpoint nor shadow the previous good one, and
    :func:`find_latest_checkpoint` can reject corrupted survivors.

    Alongside the params a ``prefix-%04d.state`` sidecar captures the
    framework PRNG stream (``mx.random.get_state()``) merged with any
    caller ``extra_state`` (e.g. data-iterator position from
    ``DataIter.state_dict()``), closing the deterministic-replay gap: a
    resume that restores the sidecar replays the exact stochastic
    schedule and batch sequence the original run would have seen.

    ``max_to_keep`` prunes the retention ring down to the newest N
    epochs after the new one lands (the ``last_good``-marked epoch is
    never pruned); ``mark_last_good`` stamps this epoch as the rollback
    target :func:`find_latest_checkpoint` prefers."""
    import pickle

    from . import random as _random
    from .filesystem import atomic_write

    if symbol is not None:
        symbol.save("%s-symbol.json" % prefix)
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    nd.save(param_name, save_dict, checksum=True, op="ckpt.write")
    state = {"rng": _random.get_state()}
    if extra_state:
        state.update(extra_state)
    blob = pickle.dumps(state, protocol=pickle.HIGHEST_PROTOCOL)
    atomic_write("%s-%04d.state" % (prefix, epoch),
                 lambda f: f.write(blob), checksum=False, op="ckpt.state")
    if mark_last_good:
        _mark_last_good(prefix, epoch)
    if max_to_keep is not None:
        _prune_checkpoints(prefix, int(max_to_keep))
    logging.info("Saved checkpoint to \"%s\"", param_name)


def _last_good_path(prefix):
    return "%s-last-good" % prefix


def _mark_last_good(prefix, epoch):
    """Atomically stamp ``epoch`` as the rollback target for ``prefix``."""
    from .filesystem import atomic_write

    atomic_write(_last_good_path(prefix),
                 lambda f: f.write(("%04d\n" % epoch).encode("ascii")),
                 checksum=False, op="ckpt.state")


def _read_last_good(prefix):
    """Epoch stamped by :func:`_mark_last_good`, or None."""
    try:
        with open(_last_good_path(prefix), "r") as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def _prune_checkpoints(prefix, max_to_keep):
    """Delete all but the newest ``max_to_keep`` epochs of ``prefix``
    (params + CRC + state sidecars).  The ``last_good``-marked epoch is
    exempt — pruning must never delete the rollback target."""
    import glob
    import re

    if max_to_keep < 1:
        return
    keep_always = _read_last_good(prefix)
    epochs = []
    for path in glob.glob("%s-[0-9][0-9][0-9][0-9].params" % prefix):
        m = re.search(r"-(\d{4})\.params$", path)
        if m:
            epochs.append(int(m.group(1)))
    for ep in sorted(epochs, reverse=True)[max_to_keep:]:
        if ep == keep_always:
            continue
        for suffix in (".params", ".params.crc32", ".state"):
            try:
                os.remove("%s-%04d%s" % (prefix, ep, suffix))
            except OSError:
                pass


def load_checkpoint_state(prefix, epoch, restore_rng=False):
    """Read the ``.state`` sidecar written by :func:`save_checkpoint`
    (None for a pre-sidecar checkpoint).  ``restore_rng`` feeds the
    captured PRNG stream straight back into ``mx.random`` so the resumed
    run continues the original stochastic schedule bit-exactly."""
    import pickle

    from . import random as _random

    try:
        with open("%s-%04d.state" % (prefix, epoch), "rb") as f:
            state = pickle.load(f)
    except OSError:
        return None
    if restore_rng and "rng" in state:
        _random.set_state(state["rng"])
    return state


def _checkpoint_ok(path):
    """Is ``path`` a loadable .params file?  CRC sidecar verdict when one
    exists; otherwise (pre-sidecar artifact, or a torn temp another writer
    left behind) a cheap container-magic sniff."""
    import struct

    from .filesystem import verify_crc_sidecar

    verdict = verify_crc_sidecar(path)
    if verdict is not None:
        return verdict
    try:
        with open(path, "rb") as f:
            head = f.read(8)
        return (len(head) == 8 and
                struct.unpack("<Q", head)[0] == nd._MAGIC)
    except OSError:
        return False


def find_latest_checkpoint(prefix, prefer_last_good=True):
    """Newest saved epoch for ``prefix`` (prefix-%04d.params), or None.

    The discovery half of checkpoint-based fault tolerance: a relaunched
    worker resumes from here instead of a hand-passed --load-epoch
    (reference mechanism: example/image-classification/common/fit.py
    --load-epoch; the launcher's --auto-resume mode relies on this).
    Partial or corrupt files (CRC sidecar mismatch, bad container magic)
    are skipped, so a crash during save rolls resume back to the newest
    INTACT epoch instead of wedging every relaunch on a torn file.

    When the training guardian has stamped a ``last_good`` marker
    (``prefix-last-good``), that epoch wins over anything newer: epochs
    past the marker may carry numerically-poisoned parameters the
    guardian was rolling away from when the process died.  Pass
    ``prefer_last_good=False`` for the raw newest-intact scan."""
    import glob
    import re

    if prefer_last_good:
        marked = _read_last_good(prefix)
        if marked is not None and \
                _checkpoint_ok("%s-%04d.params" % (prefix, marked)):
            return marked
    best = None
    for path in glob.glob("%s-[0-9][0-9][0-9][0-9].params" % prefix):
        m = re.search(r"-(\d{4})\.params$", path)
        if not m:
            continue
        if not _checkpoint_ok(path):
            logging.warning("skipping corrupt checkpoint %s", path)
            continue
        ep = int(m.group(1))
        best = ep if best is None else max(best, ep)
    return best


def load_checkpoint(prefix, epoch):
    """Load (symbol, arg_params, aux_params) from a checkpoint (reference
    model.py:349)."""
    symbol = sym.load("%s-symbol.json" % prefix)
    save_dict = nd.load("%s-%04d.params" % (prefix, epoch))
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        tp, _, name = k.partition(":")
        if tp == "arg":
            arg_params[name] = v
        if tp == "aux":
            aux_params[name] = v
    return (symbol, arg_params, aux_params)


class FeedForward:
    """Legacy training API (reference model.py FeedForward) — a thin adapter
    over mx.mod.Module kept so reference scripts port unchanged."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=None, numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, **kwargs):
        from .initializer import Uniform

        self.symbol = symbol
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.optimizer = optimizer
        self.initializer = initializer if initializer is not None else Uniform(0.01)
        self.numpy_batch_size = numpy_batch_size
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.begin_epoch = begin_epoch
        self.kwargs = kwargs.copy()
        self._module = None

    def _get_module(self, data, label_name="softmax_label"):
        from .module import Module
        from .io import DataDesc

        data_names = [d[0] for d in data.provide_data]
        label_names = [l[0] for l in data.provide_label] or [label_name]
        mod = Module(self.symbol, data_names=data_names,
                     label_names=label_names, context=self.ctx)
        return mod

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, monitor=None,
            eval_end_callback=None, eval_batch_end_callback=None):
        train_data = self._as_iter(X, y)
        mod = self._get_module(train_data)
        optimizer_params = dict(self.kwargs)
        optimizer_params.setdefault("learning_rate", 0.01)
        mod.fit(train_data, eval_data=eval_data, eval_metric=eval_metric,
                epoch_end_callback=epoch_end_callback,
                batch_end_callback=batch_end_callback, kvstore=kvstore,
                optimizer=self.optimizer, optimizer_params=optimizer_params,
                initializer=self.initializer, arg_params=self.arg_params,
                aux_params=self.aux_params, begin_epoch=self.begin_epoch,
                num_epoch=self.num_epoch, monitor=monitor)
        self._module = mod
        self.arg_params, self.aux_params = mod.get_params()
        return self

    def predict(self, X, num_batch=None):
        data = self._as_iter(X, None)
        if self._module is None:
            mod = self._get_module(data)
            mod.bind(data_shapes=data.provide_data, for_training=False)
            mod.set_params(self.arg_params or {}, self.aux_params or {},
                           allow_missing=False)
            self._module = mod
        outs = self._module.predict(data, num_batch=num_batch)
        return outs.asnumpy() if hasattr(outs, "asnumpy") else outs

    def score(self, X, y=None, eval_metric="acc"):
        data = self._as_iter(X, y)
        if self._module is None:  # e.g. right after FeedForward.load
            mod = self._get_module(data)
            mod.bind(data_shapes=data.provide_data,
                     label_shapes=data.provide_label, for_training=False)
            mod.set_params(self.arg_params or {}, self.aux_params or {},
                           allow_missing=False)
            self._module = mod
        res = self._module.score(data, eval_metric)
        return res[0][1]

    def _as_iter(self, X, y):
        from .io import DataIter, NDArrayIter

        if isinstance(X, DataIter):
            return X
        return NDArrayIter(X, y, self.numpy_batch_size, shuffle=False)

    def save(self, prefix, epoch=None):
        if epoch is None:
            epoch = self.num_epoch
        save_checkpoint(prefix, epoch, self.symbol, self.arg_params or {},
                        self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch, **kwargs)
