"""Module — the primary training API over (symbol, data, label).

Parity: /root/reference/python/mxnet/module/module.py:323-566.  Binding
builds a mesh-wide DataParallelExecutorGroup (one jitted executor, batch
sharded over contexts) instead of per-device executors; update() keeps both
reference paths — centralized kvstore update and replicated local updater.
"""
from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional

import numpy as np

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from .. import profiler as _prof
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..initializer import Uniform, InitDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


def _leaves_bytes(*param_dicts):
    """A ``start:params`` span's args for parameter dicts."""
    return _prof.leaves_bytes(v for d in param_dicts
                              for v in (d or {}).values())


def _state_leaves(state):
    """The arrays of one optimizer state (an NDArray, a tuple of them, or
    None)."""
    if state is None:
        return []
    if isinstance(state, (tuple, list)):
        return [leaf for s in state for leaf in _state_leaves(s)]
    return [state]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, compute_dtype=None, dist_mesh=None):
        super().__init__(logger=logger)
        # dist_mesh: None (auto) spans the executor mesh over every process
        # when running under jax.distributed — the TPU-native dist_sync data
        # plane; False forces a process-local module (e.g. a per-worker
        # oracle/eval model inside a distributed job)
        self._dist_mesh = dist_mesh
        # TPU-native mixed precision: compute in bf16, keep f32 master
        # params/grads/optimizer state (no reference equivalent — the
        # reference casts the symbol to fp16 instead)
        self._compute_dtype = compute_dtype
        if context is None:
            context = ctx_mod.current_context()
        if isinstance(context, ctx_mod.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = list(state_names or [])
        self._output_names = symbol.list_outputs()
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused_ok = False
        self._fused_pending = None
        self._tm_mon = None  # telemetry.StepMonitor, created when enabled

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a saved checkpoint (reference module.py:86)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Save symbol + params (+ optimizer states) (reference
        module.py:106)."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # ------------------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outs = self._exec_group.get_outputs()
        return list(zip(self._output_names, [o.shape for o in outs])) \
            if outs else []

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        """Initialize parameters (reference module.py:237)."""
        if self.params_initialized and not force_init:
            logging.warning(
                "Parameters already initialized and force_init=False. "
                "init_params call ignored.")
            return
        assert self.binded, "call bind before initializing the parameters"
        with _prof.Frame("start:params", "startup") as span:
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing)
            span.set(**_leaves_bytes(self._arg_params, self._aux_params))

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing):
        if initializer is None and not (arg_params and aux_params):
            initializer = Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(arr.shape, dtype=arr.dtype)
                for name, arr in zip(self._exec_group.param_names,
                                     self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(arr.shape, dtype=arr.dtype)
                for name, arr in zip(self._exec_group.aux_names,
                                     self._exec_group.aux_arrays)}

        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    if tuple(cache_arr.shape) != tuple(arr.shape):
                        raise MXNetError(
                            "shape mismatch for %s: loaded %s vs expected %s"
                            % (name, cache_arr.shape, arr.shape))
                    arr[:] = cache_arr
            else:
                if not allow_missing and cache is not None:
                    raise RuntimeError(
                        "%s is not presented in the provided arg_params" % name)
                if initializer is not None:
                    initializer(InitDesc(name, attrs.get(name)), arr)

        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init)
            return
        if self.params_initialized and not force_init:
            logging.warning(
                "Parameters already initialized and force_init=False. "
                "set_params call ignored.")
            return
        with _prof.Frame("start:params", "startup",
                         _leaves_bytes(arg_params, aux_params)):
            self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True

    # ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", mesh=None, partition_rules=None):
        """Bind executors (reference module.py:323).

        ``mesh`` / ``partition_rules`` opt into GSPMD sharding: a named
        device mesh (``jax.sharding.Mesh``, a ``sharding.MeshConfig``, or
        the string form ``"data=-1,model=2"``) plus regex partition rules
        (a ``sharding.PartitionRules``, a preset name, or a raw
        ``[(regex, PartitionSpec), ...]`` list).  The batch shards on the
        leading mesh axis; parameters follow their matching rule; the
        fused train step lowers once under the resulting shardings.  With
        neither given, ``MXNET_SHARDING_MESH`` / ``MXNET_SHARDING_RULES``
        activate a layout from the environment; with nothing set the
        replicated data-parallel path is unchanged."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        assert not (for_training is False and inputs_need_grad)
        with _prof.Frame("start:bind", "startup", {
                "kind": "train" if for_training else "predict"}) as span:
            self._bind(data_shapes, label_shapes, shared_module, grad_req,
                       mesh, partition_rules)
            span.set(bucket=self._exec_group.batch_size)

    def _bind(self, data_shapes, label_shapes, shared_module, grad_req, mesh,
              partition_rules):
        for_training = self.for_training
        inputs_need_grad = self.inputs_need_grad
        self._data_shapes = self._exec_group_descs(data_shapes)
        self._label_shapes = self._exec_group_descs(label_shapes) \
            if label_shapes else None

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        if mesh is None and partition_rules is None:
            from ..base import env

            env_mesh = env("MXNET_SHARDING_MESH", "", str)
            env_rules = env("MXNET_SHARDING_RULES", "", str)
            if env_mesh:
                mesh = env_mesh
            if env_rules:
                partition_rules = env_rules

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group, self.logger,
            self._fixed_param_names, grad_req, state_names=self._state_names,
            compute_dtype=self._compute_dtype, dist_mesh=self._dist_mesh,
            mesh=mesh, partition_rules=partition_rules)
        self._total_exec_bytes = 0
        if _telemetry.enabled() and self._exec_group._mesh is not None:
            self._telemetry_monitor().note_mesh(self._exec_group._mesh)

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    @staticmethod
    def _exec_group_descs(shapes):
        from ..io import DataDesc

        out = []
        for s in shapes:
            out.append(s if isinstance(s, DataDesc) else DataDesc(s[0], s[1]))
        return out

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = self._exec_group_descs(data_shapes)
        self._label_shapes = self._exec_group_descs(label_shapes) \
            if label_shapes else None
        self._exec_group.reshape(self._data_shapes, self._label_shapes)

    # ------------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install optimizer + kvstore (reference module.py:432)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _prof.Frame("start:optimizer", "startup") as span:
            self._init_optimizer(kvstore, optimizer, optimizer_params)
            # the fused step makes its states at its first call: what is
            # here now is what a checkpoint or a kvstore brought
            held = _prof.leaves_bytes(
                leaf for state in (self._updater.states.values()
                                   if self._updater is not None else ())
                for leaf in _state_leaves(state))
            span.set(states=held["leaves"], bytes=held["bytes"])

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        if self._exec_group._multiprocess:
            if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
                # global-mesh sync DP: the gradient all-reduce is compiled
                # into the (fused) step over the multi-process mesh, and
                # every worker applies the identical update to its replica —
                # the kvstore degrades to a control-plane facade (init
                # broadcast, barrier, rank), replacing the reference's
                # server-side merge (kvstore_dist_server.h:164-200)
                update_on_kvstore = False
            elif kvstore and "dist" in kvstore.type:
                # dist_async needs each worker's OWN gradient at the server;
                # the mesh has already summed them — the two data planes
                # cannot compose
                raise MXNetError(
                    "dist_async requires per-worker gradients: construct "
                    "the Module with dist_mesh=False to train process-local "
                    "replicas against the parameter server")

        if kvstore and update_on_kvstore:
            # centralized-update path: ride the async comm engine so
            # push/pull overlap compute (MXNET_KVSTORE_ASYNC=0 restores
            # the synchronous loop; no-op if already wrapped)
            from ..comm_engine import maybe_async

            kvstore = maybe_async(kvstore)

        batch_size = self._exec_group.batch_size
        if self._exec_group._multiprocess:
            # gradients are summed over the GLOBAL batch by the compiled
            # psum regardless of kvstore type, so the default grad scale
            # must account for every process's shard
            import jax

            batch_size *= jax.process_count()
        elif kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            # one mesh executor regardless of len(context): updater indices
            # are plain param positions (the reference's per-device
            # i*ndev+k scheme only applies to its one-executor-per-device
            # layout, executor_group.py:77)
            idx2name = dict(enumerate(self._exec_group.param_names))
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but "
                    "rescale_grad is not normalized to 1.0/batch_size/"
                    "num_workers (%s vs. %s). Is this intended?",
                    optimizer.rescale_grad, rescale_grad)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore,
                                skip_indices=self._sparse_param_indices())
            if not update_on_kvstore and "dist" in kvstore.type and \
                    self._exec_group._multiprocess:
                # pull the rank-0-broadcast init back so every replica
                # starts identical (reference inits from rank 0 only,
                # kvstore_dist.h:64-82); afterwards the kvstore data plane
                # is out of the training loop
                for idx, name in enumerate(self._param_names):
                    kvstore.pull(idx, self._arg_params[name], priority=-idx)
                self._exec_group.set_params(self._arg_params,
                                            self._aux_params)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        if kvstore and "dist" in kvstore.type and \
                os.environ.get("MXNET_KVSTORE_ELASTIC", "0") == "1":
            # elastic preemption path (fault_tolerance.md §elasticity):
            # SIGTERM drains in-flight comm ops, checkpoints if the user
            # registered save hooks, leaves the membership table, and
            # exits clean so launch.py counts a preemption, not a crash
            from ..kvstore import install_preemption_handler

            install_preemption_handler(kvstore)

        self.optimizer_initialized = True
        self._fused_ok = self._decide_fused()

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _sparse_param_indices(self):
        """Param indices routed around the dense kvstore path entirely.
        The base Module has none; SparseEmbeddingModule returns its
        row_sparse slots, whose tables live sharded on the servers and
        must never be init'd (or pushed) as dense tensors."""
        return ()

    def _decide_fused(self):
        """Whether update() can run as ONE jitted fwd+bwd+optimizer program
        (Executor.fused_step).  Requires the replicated-updater path (no
        server-side aggregation), an optimizer with a traceable update rule,
        plain grad_req='write', and no monitor hook (which needs eager
        internals).  MXNET_FUSED_STEP=0 is the escape hatch back to the
        reference-style eager per-key loop."""
        from ..base import env

        if env("MXNET_FUSED_STEP", "1", str) == "0":
            return False
        from .. import faults as _faults
        if _faults.targets_corruption("guardian.grad"):
            # scheduled gradient corruption (nan/bitflip fault injection)
            # rewrites host-visible grad buffers; the fused step never
            # materializes them, so fall back to the eager loop
            return False
        if self._update_on_kvstore or self._updater is None:
            return False
        if self._kvstore is not None and "dist" in self._kvstore.type \
                and not self._exec_group._multiprocess:
            # single-process dist (degenerate 1-worker run): keep the eager
            # kvstore loop; with a real multi-process mesh the fused step
            # carries the compiled psum and the kvstore is a facade
            return False
        if not type(self._optimizer).has_pure_update():
            return False
        if any(self._exec_group.grad_req.get(n) == "add"
               for n in self._param_names):
            return False
        if self.inputs_need_grad:  # fused step differentiates params only
            return False
        if self._exec_group._monitor_callback is not None:
            return False
        return True

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    def _wait_async_comm(self):
        """Drain deferred kvstore traffic before parameters are read.
        update() leaves pushes/pulls in flight on an async kvstore so
        they overlap the next batch's host-side prep; the executor reads
        raw param buffers (no NDArray read guard fires), so the overlap
        window closes here."""
        kv = getattr(self, "_kvstore", None)
        if kv is not None and getattr(self, "_update_on_kvstore", False):
            wait_all = getattr(kv, "wait_all", None)
            if wait_all is not None:
                wait_all()

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        # run any deferred fused batch first so its grads/outputs are not
        # interleaved with (or clobbered by) this forward
        self._flush_fused_pending()
        self._wait_async_comm()
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._flush_fused_pending()
        self._exec_group.backward(out_grads=out_grads)

    def _telemetry_monitor(self):
        """Per-module StepMonitor, created on first use; callers must gate
        on ``telemetry.enabled()`` so the off path allocates nothing."""
        from .. import telemetry as _tm

        if self._tm_mon is None:
            self._tm_mon = _tm.StepMonitor(_tm)
        return self._tm_mon

    def forward_backward(self, data_batch):
        """Fused forward+backward — one XLA program per batch.  When the
        fully-fused step is enabled, execution is deferred to update() so
        forward, backward, AND the optimizer run as a single donated XLA
        program (see _decide_fused)."""
        assert self.binded and self.params_initialized
        with _prof.Frame("Module.forward_backward", "module"):
            if _telemetry.enabled():
                mon = self._telemetry_monitor()
                mon.step_begin()
                mon.note_batch(data_batch)  # recompile fingerprint
            if self._fused_ok and self.optimizer_initialized:
                self._fused_pending = data_batch
                return
            # this path does NOT go through self.forward(), so the async
            # overlap window from the previous update() closes here
            self._wait_async_comm()
            self._exec_group.forward_backward(data_batch)

    def _flush_fused_pending(self):
        """A caller wants grads/outputs before update(): fall back to the
        two-phase path for this batch."""
        if self._fused_pending is not None:
            batch, self._fused_pending = self._fused_pending, None
            self._exec_group.forward_backward(batch)

    def update(self):
        """Apply the optimizer to every parameter (reference module.py:553).
        On the fused path this runs the whole pending train step as one
        compiled program; otherwise the reference's eager per-key
        push/pull/updater loop."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        with _prof.Frame("Module.update", "module"):
            self._update()

    def _update(self):
        self._params_dirty = True
        self._guardian_action = "ok"
        if self._fused_pending is not None:
            batch, self._fused_pending = self._fused_pending, None
            self._exec_group.fused_step(batch, self._optimizer, self._updater)
            g = getattr(self, "_guardian", None)
            if g is not None and self._exec_group.execs:
                # the on-device guard already gated the poisoned update out
                # with a where(); this read lands where the step syncs
                # anyway (metric update) and only feeds the response ladder
                verdict = getattr(self._exec_group.execs[0],
                                  "_guard_verdict", None)
                if verdict is not None:
                    ok, gnorm = verdict
                    self._guardian_action = g.observe(finite=bool(ok),
                                                      gnorm=float(gnorm))
            if _telemetry.enabled():
                self._telemetry_step_end()
            return
        from .. import faults as _faults
        if _faults.targets_corruption("guardian.grad"):
            self._corrupt_grads()
        if self._update_on_kvstore:
            # pushes go out in backward order (the order grads become
            # available) with priority=-index; the wait is deferred so an
            # async kvstore overlaps comms with metric/update + the next
            # batch fetch — forward() closes the window
            _update_params_on_kvstore(
                self._exec_group.param_arrays,
                self._exec_group.grad_arrays,
                self._kvstore,
                param_order=self._exec_group.backward_param_order(),
                defer_wait=True)
        else:
            # on a multi-process mesh the gradients coming out of the
            # executor are already globally summed (the psum is compiled
            # into the backward), so the kvstore must NOT reduce them again
            kv = self._kvstore
            if kv is not None and self._exec_group._multiprocess:
                kv = None
            if self._guardian_observe_eager() != "ok":
                # anomalous batch: leave params/updater state untouched —
                # the eager-path equivalent of the fused guard's where()
                if _telemetry.enabled():
                    self._telemetry_step_end()
                return
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=1,
                           kvstore=kv)
        if _telemetry.enabled():
            self._telemetry_step_end()

    def _each_grad(self):
        for arr in self._exec_group.grad_arrays:
            for a in (arr if isinstance(arr, list) else [arr]):
                if a is not None:
                    yield a

    def _corrupt_grads(self):
        """Run every host-visible gradient past the fault plan's corrupt
        hook (nan/bitflip kinds on the ``guardian.grad`` op); an armed rule
        rewrites the chosen element in place.  Only reached when a plan
        actually targets corruption (update() pre-checks), so the normal
        path never pays the host transfer."""
        from .. import faults as _faults

        for a in self._each_grad():
            before = a.asnumpy()
            after = _faults.corrupt("guardian.grad", before)
            if after is not before:
                a[:] = after

    def _guardian_observe_eager(self):
        """Host-side guard for the eager update path: finiteness + global
        grad-norm over every gradient, fed to the guardian's response
        ladder.  Returns the action ("ok" = apply this batch)."""
        g = getattr(self, "_guardian", None)
        if g is None:
            return "ok"
        finite = True
        # accumulate the norm in f32, matching the fused guard: a
        # finite-but-huge corruption (exponent bit-flip ~1e38) overflows
        # the square-sum and reads as non-finite right here, with no
        # spike history needed
        sq = np.float32(0)
        with np.errstate(over="ignore"):  # overflow IS the signal
            for a in self._each_grad():
                v = np.asarray(a.asnumpy(), dtype=np.float32)
                if not np.all(np.isfinite(v)):
                    finite = False
                    break
                sq += np.sum(np.square(v))
        gnorm = float(np.sqrt(sq)) if finite else float("inf")
        self._guardian_action = g.observe(finite=finite, gnorm=gnorm)
        return self._guardian_action

    def _telemetry_step_end(self):
        """Close the step span: batch size, wall time, and — on the fused
        path's compile misses — one XLA cost analysis for MFU."""
        mon = self._telemetry_monitor()
        ex = self._exec_group.execs[0] if self._exec_group.execs else None
        if ex is not None and getattr(ex, "_fused_new_compile", False):
            ex._fused_new_compile = False
            mon.note_compile(ex)
        mon.step_end(getattr(self._exec_group, "batch_size", 0))

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        self._flush_fused_pending()
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        self._flush_fused_pending()
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._flush_fused_pending()
        self._exec_group.update_metric(eval_metric, labels)

    # ------------------------------------------------------------------
    def _sync_params_from_devices(self):
        self._wait_async_comm()
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        self._fused_ok = False  # monitor needs eager per-tensor internals
        self._flush_fused_pending()
        self._exec_group.install_monitor(mon)
