"""DataParallelExecutorGroup — the data-parallel engine of the frontend.

TPU-native redesign of /root/reference/python/mxnet/module/executor_group.py:77.
The reference binds ONE executor per device, slices the batch in Python
(`decide_slices` :207, `_load_data` :43), and reduces gradients through
KVStore/Comm.  Here there is ONE executor jitted over a `jax.sharding.Mesh`
of all given contexts: the batch is sharded on the mesh's 'data' axis, the
parameters are replicated, and XLA's SPMD partitioner inserts the gradient
all-reduce (the Comm/KVStore reduce compiled into the step — ICI collectives
instead of PCIe/host staging).  `workload` (work_load_list) is accepted for
API parity but even splits are the only mesh-friendly layout, so uneven
splits are rejected rather than silently ignored.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from .. import context as ctx_mod
from .. import ndarray as nd
from .. import profiler as _prof
from ..executor import Executor
from ..io import DataDesc

__all__ = ["DataParallelExecutorGroup"]


def _merge_shape(desc, batch_size):
    return (batch_size,) + tuple(desc.shape[1:])


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None, compute_dtype=None,
                 dist_mesh=None, mesh=None, partition_rules=None):
        self.symbol = symbol
        self.contexts = contexts
        self.compute_dtype = compute_dtype
        if workload and len(set(workload)) > 1:
            raise MXNetError(
                "work_load_list with uneven splits is unsupported on a device "
                "mesh: SPMD sharding requires equal shards per device")
        self.param_names = list(param_names)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = list(fixed_param_names or [])
        self.state_names = list(state_names or [])
        self.logger = logger
        self._monitor_callback = None

        if grad_req != "null" and for_training:
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = ("null" if k in self.fixed_param_names
                                        else grad_req)
                elif k in [d.name if isinstance(d, DataDesc) else d[0]
                           for d in data_shapes]:
                    self.grad_req[k] = grad_req if inputs_need_grad else "null"
                else:
                    self.grad_req[k] = "null"
        else:
            self.grad_req = {k: "null" for k in self.arg_names}

        self._mesh = None
        self._data_sharding = None
        self._repl_sharding = None
        self._multiprocess = False
        self._rules = None        # PartitionRules (GSPMD rule path)
        self._param_specs = None  # resolved {name: PartitionSpec} at bind
        self._data_axis = "data"
        import jax

        if mesh is not None or partition_rules is not None:
            # GSPMD rule path: an explicit named mesh (possibly multi-axis,
            # e.g. ("data", "model")) + regex partition rules.  The batch
            # shards on the LEADING axis; parameters follow their rule's
            # PartitionSpec, resolved at bind once shapes are inferred.
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            from .. import sharding as _sharding

            self._rules = _sharding.as_rules(
                partition_rules if partition_rules is not None
                else "replicated")
            if not isinstance(mesh, Mesh):
                mesh = _sharding.build_mesh(mesh if mesh is not None
                                            else "data=-1")
            self._mesh = mesh
            self._data_axis = mesh.axis_names[0]
            self._multiprocess = jax.process_count() > 1
            self._data_sharding = NamedSharding(mesh, P(self._data_axis))
            self._repl_sharding = NamedSharding(mesh, P())
        elif jax.process_count() > 1 and dist_mesh is not False:
            # multi-host data parallelism: ONE global mesh over every device
            # of every process; the fused step compiles the gradient psum
            # over it (TPU-native replacement for the reference's
            # ps-lite push/pull, src/kvstore/kvstore_dist.h:183-230 — the
            # collective rides ICI/DCN inside the step instead of a host
            # round-trip per key)
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            self._multiprocess = True
            self._mesh = Mesh(np.asarray(jax.devices()), ("data",))
            self._data_sharding = NamedSharding(self._mesh, P("data"))
            self._repl_sharding = NamedSharding(self._mesh, P())
        elif len(contexts) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            devices = [c.jax_device() for c in contexts]
            self._mesh = Mesh(np.array(devices), ("data",))
            self._data_sharding = NamedSharding(self._mesh, P("data"))
            self._repl_sharding = NamedSharding(self._mesh, P())

        self.batch_size = None
        self.slices = None
        self.execs: List[Executor] = []
        self.data_arrays = None
        self.label_arrays = None
        self.param_arrays = None
        self.grad_arrays = None
        self.aux_arrays = None
        self.input_grad_arrays = None
        self.data_shapes = None
        self.label_shapes = None
        self.data_names = None
        self.label_names = None
        self.data_layouts = None
        self.label_layouts = None
        self.output_layouts = None
        self.num_outputs = None
        self.bind_exec(data_shapes, label_shapes, shared_group)

    # ------------------------------------------------------------------
    def decide_slices(self, data_shapes):
        """Batch → per-device slices (reference executor_group.py:207).  On
        the mesh the split is implicit in the sharding; slices are kept for
        API parity (e.g. Monitor output naming)."""
        assert len(data_shapes) > 0
        major_axis = [DataDesc.get_batch_axis(getattr(s, "layout", "NCHW"))
                      for s in data_shapes]
        for (name, shape), axis in zip(
                [(getattr(s, "name", s[0]), getattr(s, "shape", None) or s[1])
                 for s in data_shapes], major_axis):
            if axis == -1:
                continue
            batch_size = shape[axis]
            if self.batch_size is not None:
                assert batch_size == self.batch_size, \
                    "all data must have the same batch size"
            else:
                self.batch_size = batch_size
                if self._rules is not None:
                    # explicit mesh: the batch splits over the leading
                    # ('data') axis only — a ("data","model") 4x2 mesh
                    # shards the batch 4 ways
                    import jax

                    n = int(self._mesh.shape[self._data_axis])
                    if self._multiprocess:
                        # per-process batch; each process feeds its shard
                        n = max(1, n // jax.process_count())
                elif self._multiprocess:
                    import jax

                    # per-process batch; each process feeds its local devices
                    n = jax.local_device_count()
                else:
                    n = len(self.contexts)
                if batch_size % n != 0:
                    raise MXNetError(
                        "batch size %d is not divisible by the %d-way 'data' "
                        "split of the mesh" % (batch_size, n))
                step = batch_size // n
                self.slices = [slice(i * step, (i + 1) * step)
                               for i in range(n)]
        return major_axis

    def _as_desc(self, shapes):
        out = []
        for s in shapes or []:
            if isinstance(s, DataDesc):
                out.append(s)
            else:
                out.append(DataDesc(s[0], s[1]))
        return out

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        """Bind the single mesh executor (reference binds one per device via
        _bind_ith_exec :538)."""
        self.data_shapes = self._as_desc(data_shapes)
        self.label_shapes = self._as_desc(label_shapes) if label_shapes else []
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [l.name for l in self.label_shapes]
        self.data_layouts = self.decide_slices(self.data_shapes)
        if self.label_shapes:
            self.label_layouts = self.decide_slices(self.label_shapes)

        input_shapes = {d.name: d.shape for d in self.data_shapes}
        input_shapes.update({l.name: l.shape for l in self.label_shapes})
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        if arg_shapes is None:
            raise MXNetError("shape inference failed at bind")

        input_types = {d.name: getattr(d, "dtype", np.float32)
                       for d in self.data_shapes + self.label_shapes}
        arg_types, _, aux_types = self.symbol.infer_type(**input_types)

        shared_exec = shared_group.execs[0] if shared_group else None
        ctx0 = self.contexts[0]
        shared_pool = shared_exec.arg_dict if shared_exec else {}

        args = {}
        grads = {}
        for name, shape, dtype in zip(self.arg_names, arg_shapes, arg_types):
            if shared_exec is not None and name in self.param_names and \
                    name in shared_pool:
                args[name] = shared_pool[name]  # bucketing shares param memory
            else:
                args[name] = nd.zeros(shape, ctx0, dtype=dtype)
            if self.grad_req.get(name, "null") != "null":
                grads[name] = nd.zeros(shape, ctx0, dtype=dtype)
        aux = {}
        shared_aux = shared_exec.aux_dict if shared_exec else {}
        for name, shape, dtype in zip(self.aux_names, aux_shapes, aux_types):
            if name in shared_aux and \
                    tuple(shared_aux[name].shape) == tuple(shape):
                aux[name] = shared_aux[name]
            else:
                aux[name] = nd.zeros(shape, ctx0, dtype=dtype)

        executor = Executor(self.symbol, ctx0, args, grads or None,
                            self.grad_req, aux, shared_exec=shared_exec,
                            compute_dtype=self.compute_dtype,
                            cast_exclude=self.label_names)
        self.execs = [executor]
        if self._mesh is not None:
            executor._kernel_mesh = (self._mesh, self._data_axis)
        if self._rules is not None:
            self._apply_rule_shardings(
                executor,
                {n: tuple(s) for n, s in zip(self.arg_names, arg_shapes)},
                {n: tuple(s) for n, s in zip(self.aux_names, aux_shapes)})
        elif self._mesh is not None:
            self._apply_shardings(executor)

        # parity views: param_arrays/grad_arrays are lists over "devices";
        # with one mesh executor each entry is the single (sharded) array.
        self.param_arrays = [executor.arg_dict[name]
                             for name in self.param_names]
        self.grad_arrays = [executor.grad_dict.get(name)
                            for name in self.param_names]
        self.aux_arrays = [executor.aux_dict[name] for name in self.aux_names]
        self.data_arrays = [executor.arg_dict[name] for name in self.data_names]
        self.label_arrays = [executor.arg_dict[name]
                             for name in self.label_names]
        self.input_grad_arrays = [executor.grad_dict.get(name)
                                  for name in self.data_names] \
            if self.inputs_need_grad else []
        self.num_outputs = len(self.symbol.list_outputs())
        if self._monitor_callback is not None:
            executor.set_monitor_callback(self._monitor_callback)

    def backward_param_order(self):
        """Parameter indices in the order their gradients become available
        — last layer first.  ``param_names`` follows the symbol's
        topological (forward) order, so the reverse approximates backward
        completion order; the centralized update path issues kvstore
        pushes in this order so late-layer gradients hit the wire while
        early layers are conceptually still being produced (reference
        kvstore priority scheduling, kvstore_dist.h + engine)."""
        return list(range(len(self.param_names) - 1, -1, -1))

    def _replicate(self, x):
        """Place a process-local array as fully-replicated on the (possibly
        multi-process) mesh.  Arrays already equivalently placed pass
        through untouched — so ``set_params`` with pre-sharded arrays (a
        checkpoint restored onto the mesh) is a placement no-op instead of
        a spurious copy or a cross-process error."""
        from ..sharding import place

        return place(x, self._mesh, self._repl_sharding.spec)

    def _apply_rule_shardings(self, executor, arg_shapes, aux_shapes):
        """Resolve the regex rules against the inferred shapes and hand the
        whole layout to ``Executor.set_shardings``: batch inputs shard on
        the leading mesh axis, every other arg/aux gets its rule's
        PartitionSpec.  From here on every write path (set_params, batch
        loads, the fused step's in_shardings) follows the same specs."""
        from jax.sharding import PartitionSpec as P

        from .. import sharding as _sharding
        from ..base import env

        batch_names = set(self.data_names) | set(self.label_names)
        ruled = {name: shape
                 for name, shape in list(arg_shapes.items())
                 + list(aux_shapes.items()) if name not in batch_names}
        specs = self._rules.match(ruled)
        if env("MXNET_SHARDING_VALIDATE", 1, int):
            _sharding.validate_specs(self._mesh, specs, ruled)
        if env("MXNET_SHARDING_EXPLAIN", 0, int):
            self.logger.info(
                "partition rules (%s) on mesh %s:\n%s", self._rules.name,
                _sharding.mesh_axes(self._mesh),
                self._rules.explain_str(ruled))
        self._param_specs = specs
        all_specs = dict(specs)
        for name in batch_names:
            all_specs[name] = P(self._data_axis)
        executor.set_shardings(self._mesh, all_specs)
        self._note_shard_bytes(executor)

    def _note_shard_bytes(self, executor):
        """Telemetry gauge pair making a layout's memory win a number:
        actual average per-device parameter residency vs the fully
        replicated baseline."""
        from .. import telemetry

        if not telemetry.enabled():
            return
        from .. import sharding as _sharding

        arrays = [executor.arg_dict[n] for n in self.param_names]
        arrays += [executor.aux_dict[n] for n in self.aux_names]
        per_dev, repl = _sharding.param_bytes(arrays)
        telemetry.gauge(
            "mxtpu_params_sharded_bytes",
            "Average per-device parameter+aux bytes under the active "
            "sharding").set(per_dev)
        telemetry.gauge(
            "mxtpu_params_replicated_bytes",
            "Per-device parameter+aux bytes if fully replicated").set(repl)

    def _apply_shardings(self, executor):
        """Replicate params, shard batch inputs on the 'data' axis.  XLA's
        partitioner then emits the psum for gradient aggregation (the
        compiled equivalent of Comm reduce, comm.h:120-360)."""
        import jax

        batch_names = set(self.data_names) | set(self.label_names)
        for name, arr in executor.arg_dict.items():
            if name in batch_names:
                # batch entries are re-placed per step by _load_batch; on a
                # multi-process mesh the bound placeholder stays local (its
                # global shape differs from the bound local shape)
                if not self._multiprocess:
                    arr._set(jax.device_put(arr._data, self._data_sharding))
            else:
                arr._set(self._replicate(arr._data))
        for arr in executor.aux_dict.values():
            arr._set(self._replicate(arr._data))
        for arr in executor.grad_dict.values():
            arr._set(self._replicate(arr._data))

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and \
                label_shapes == self.label_shapes:
            return
        # preserve trained parameter/aux memory across the rebind (the
        # reference reshapes executors in place, executor_group.py:378)
        old_exec = self.execs[0] if self.execs else None
        self.batch_size = None
        self.bind_exec(data_shapes, label_shapes, reshape=True)
        if old_exec is not None:
            new_exec = self.execs[0]
            for name in self.param_names:
                if name in old_exec.arg_dict:
                    new_exec.arg_dict[name]._set(old_exec.arg_dict[name]._data)
            for name in self.aux_names:
                if name in old_exec.aux_dict:
                    new_exec.aux_dict[name]._set(old_exec.aux_dict[name]._data)

    # ------------------------------------------------------------------
    def set_params(self, arg_params, aux_params):
        for executor in self.execs:
            executor.copy_params_from(arg_params, aux_params)
        if self._rules is not None:
            # copy_params_from routes through Executor._write_arg, which
            # commits each value straight onto the mesh under its spec
            # (pre-sharded arrays pass through) — nothing left to place
            return
        if self._mesh is not None:
            self._apply_shardings(self.execs[0])

    def get_params(self, arg_params, aux_params):
        """Copy current params into the given dicts (reference
        executor_group.get_params — the weighted merge across devices is a
        no-op here: the mesh keeps one replicated copy)."""
        if self._rules is not None:
            # tensor-parallel layouts: gather shards to host values first
            # (cross-process arrays are not directly indexable)
            from .. import sharding as _sharding

            executor = self.execs[0]
            for name in self.param_names:
                arg_params[name][:] = _sharding.gather_params(
                    {name: executor.arg_dict[name]})[name]
            for name in self.aux_names:
                aux_params[name][:] = _sharding.gather_params(
                    {name: executor.aux_dict[name]})[name]
            return
        for name in self.param_names:
            arg_params[name][:] = self.execs[0].arg_dict[name]
        for name in self.aux_names:
            aux_params[name][:] = self.execs[0].aux_dict[name]

    # ------------------------------------------------------------------
    def _load_batch(self, data_batch):
        """Place batch data onto the mesh (scatter ≈ _load_data :43)."""
        with _prof.Frame("ExecGroup.load_batch", "module"):
            self._place_batch(data_batch)

    def _place_batch(self, data_batch):
        import jax

        executor = self.execs[0]
        arrays = list(zip(self.data_names, data_batch.data))
        if self.label_names and getattr(data_batch, "label", None):
            arrays += list(zip(self.label_names, data_batch.label))
        expected = {d.name: tuple(d.shape)
                    for d in self.data_shapes + self.label_shapes}
        for name, src in arrays:
            dst = executor.arg_dict[name]
            if self._multiprocess:
                # every process contributes its local batch as one shard of
                # the GLOBAL batch (global batch = num_processes x local
                # batch, split on the mesh 'data' axis); the traced step
                # then runs SPMD over all hosts with the gradient psum
                # compiled in.  Host numpy feeds the global array directly —
                # no staging device round trip for numpy-backed iterators.
                host = src.asnumpy() if isinstance(src, nd.NDArray) \
                    else np.asarray(src)
                if tuple(host.shape) != expected[name]:
                    raise MXNetError(
                        "batch shape %s for %s does not match bound shape %s"
                        % (tuple(host.shape), name, expected[name]))
                if host.dtype != dst.dtype:
                    host = host.astype(dst.dtype)
                data = jax.make_array_from_process_local_data(
                    self._data_sharding, host)
            else:
                data = src._data if isinstance(src, nd.NDArray) else \
                    nd.array(src)._data
                if tuple(data.shape) != expected[name]:
                    raise MXNetError(
                        "batch shape %s for %s does not match bound shape %s"
                        % (tuple(data.shape), name, expected[name]))
                if data.dtype != dst.dtype:
                    data = data.astype(dst.dtype)
                if self._data_sharding is not None:
                    data = jax.device_put(data, self._data_sharding)
            dst._set(data)

    def forward(self, data_batch, is_train=None):
        self._load_batch(data_batch)
        if is_train is None:
            is_train = self.for_training
        self.execs[0].forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        self.execs[0].backward(out_grads)

    def forward_backward(self, data_batch):
        """Fused fwd+bwd in one XLA program — the TPU hot path."""
        self._load_batch(data_batch)
        self.execs[0].forward_backward()

    def fused_step(self, data_batch, optimizer, updater):
        """Fully-fused train step: fwd+bwd+optimizer update as ONE donated
        XLA program (Executor.fused_step) — replaces forward_backward +
        the per-key kvstore push/pull loop of the reference hot path."""
        self._load_batch(data_batch)
        self.execs[0].fused_step(optimizer, updater, self.param_names)

    def _local_view(self, arr):
        """Process-local slice of a batch-sharded global output (each worker
        sees the rows it contributed — matching the reference, where a
        worker's executor outputs cover only its own batch)."""
        if not self._multiprocess:
            return arr
        import jax.numpy as jnp

        x = arr._data
        if getattr(x, "is_fully_addressable", True):
            return arr
        shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start
                        if s.index and s.index[0].start is not None else 0)
        seen = set()
        parts = []
        for s in shards:
            key = tuple((d.start, d.stop) for d in s.index if d is not None)
            if key in seen:  # replicated output: one copy is enough
                continue
            seen.add(key)
            parts.append(s.data)
        local = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        return nd.NDArray(local, self.contexts[0])

    def get_outputs(self, merge_multi_context=True):
        return [self._local_view(o) for o in self.execs[0].outputs]

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        return [self._local_view(g) if g is not None else None
                for g in (self.execs[0].grad_dict.get(name)
                          for name in self.data_names)]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        self._monitor_callback = mon.stat_helper if hasattr(mon, "stat_helper") \
            else mon
        for executor in self.execs:
            executor.set_monitor_callback(self._monitor_callback)
