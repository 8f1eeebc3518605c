"""Executor — binds a Symbol to devices and runs it.

TPU-native redesign of the reference GraphExecutor
(/root/reference/src/executor/graph_executor.cc:322-676 and
include/mxnet/executor.h).  Where the reference runs nnvm passes (Gradient,
PlanMemory, AttachOpExecs) and pushes one engine op per node, here the whole
graph lowers to ONE pure JAX function that XLA fuses and schedules — the
"bulk exec" of the reference (InitOpSegs, graph_executor.cc:678) taken to its
logical conclusion.  Autodiff (the Gradient pass + ``_backward_*`` ops) is
``jax.vjp``; memory planning/in-place sharing is XLA buffer assignment +
donation; ``MXNET_BACKWARD_DO_MIRROR`` maps to ``jax.checkpoint``.

Semantics kept from the reference:
  * ``grad_req`` in {write, add, null} per argument (kAddTo accumulation —
    the DetectInplaceAddTo pass — is functional accumulation here),
  * auxiliary states (BatchNorm moving stats) updated on training forward,
  * ``backward(out_grads)`` head gradients; loss ops ignore them via their
    custom vjps,
  * monitor callback surface (SetMonitorCallback, graph_executor.cc:69).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .base import MXNetError, env
from .context import Context
from .ops import OpContext
from . import profiler as _prof
from . import random as _random

__all__ = ["Executor"]


class _GraphPlan:
    """Static lowering plan for a symbol: topo order, entry wiring, aux and
    stochastic bookkeeping.  Shared across executors binding the same symbol
    object (the analogue of shared_exec memory sharing in bucketing)."""

    def __init__(self, symbol):
        from .symbol import _topo_sort

        self.symbol = symbol
        self.nodes = _topo_sort(symbol._outputs)
        self.arg_names = [n.name for n in self.nodes if n.is_variable]
        self.aux_names: List[str] = []
        for n in self.nodes:
            self.aux_names.extend(n.aux_names())
        self.stochastic_nodes = [
            n for n in self.nodes if n.op is not None and n.op.stochastic]
        self.output_entries = [(id(node), idx) for node, idx in symbol._outputs]
        self.output_names = symbol.list_outputs()
        self._fingerprint = None

    def fingerprint(self) -> str:
        """Content hash of the graph (serialized symbol) — the
        process-independent half of a persistent compile-cache key."""
        if self._fingerprint is None:
            import hashlib

            self._fingerprint = hashlib.sha256(
                self.symbol.tojson().encode()).hexdigest()[:16]
        return self._fingerprint

    def placement_map(self, group2ctx):
        """Node-id → jax.Device from ``__ctx_group__`` attrs (reference:
        nnvm PlaceDevice pass + _CrossDeviceCopy splicing,
        src/executor/graph_executor.cc:230-320; here the cross-device copy
        is a jax.device_put compiled into the jitted graph)."""
        if not group2ctx:
            return {}
        placement = {}
        for n in self.nodes:
            if n.is_variable:
                continue
            # AttrScope stores the plain key; reference JSON may carry the
            # C-API-mangled "__ctx_group__" form — accept both
            group = None
            for store in (n.attr_dict, n.attrs):
                group = store.get("ctx_group") or store.get("__ctx_group__")
                if group:
                    break
            if group and group in group2ctx:
                placement[id(n)] = group2ctx[group].jax_device()
        return placement

    def run(self, args: Dict[str, Any], aux: Dict[str, Any], rng,
            is_train: bool, want_internals: bool = False, placement=None):
        """Execute the graph as a pure function of (args, aux, rng)."""
        import jax

        vals: Dict[tuple, Any] = {}
        new_aux: Dict[str, Any] = {}
        n_st = len(self.stochastic_nodes)
        keys = {}
        if n_st and rng is not None:
            subkeys = jax.random.split(rng, n_st)
            keys = {id(n): subkeys[i] for i, n in enumerate(self.stochastic_nodes)}
        for n in self.nodes:
            if n.is_variable:
                if n.name not in args:
                    raise MXNetError("missing argument %r" % n.name)
                vals[(id(n), 0)] = args[n.name]
                continue
            ins = [vals[(id(p), idx)] for p, idx in n.inputs]
            aux_in = tuple(aux[a] for a in n.aux_names())
            opctx = OpContext(is_train=is_train, rng=keys.get(id(n)))
            if placement and id(n) in placement:
                dev = placement[id(n)]
                ins = [jax.device_put(x, dev) for x in ins]
            # every operation of this node, forward and (as
            # ``transpose(jvp(<name>))``) backward, carries the node's name
            # into the compiled program and from there into a device trace
            with jax.named_scope(n.name):
                outs, aux_out = n.op.apply(opctx, n.attrs, ins, aux_in)
            for i, o in enumerate(outs):
                vals[(id(n), i)] = o
            for aname, a in zip(n.aux_names(), aux_out):
                new_aux[aname] = a
        outputs = [vals[e] for e in self.output_entries]
        if want_internals:
            internals = {}
            for n in self.nodes:
                if n.is_variable:
                    continue
                for i in range(n.num_outputs()):
                    oname = n.op.output_names(n.attrs, n.name)[i]
                    internals[oname] = vals[(id(n), i)]
            return outputs, new_aux, internals
        return outputs, new_aux


class Executor:
    def __init__(self, symbol, ctx: Context, args, args_grad=None,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec: Optional["Executor"] = None,
                 compute_dtype=None, cast_exclude=()):
        from . import ndarray as nd

        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx or {}
        # mixed precision: float32 args are cast to compute_dtype (bf16 on
        # TPU) inside the traced step; master params/grads/aux stay float32.
        # cast_exclude holds names that must keep full precision (labels —
        # bf16 cannot represent class ids > 256 exactly).
        self._compute_dtype = compute_dtype
        self._cast_exclude = frozenset(cast_exclude)
        if shared_exec is not None and shared_exec._symbol is symbol:
            self._plan = shared_exec._plan
        else:
            self._plan = _GraphPlan(symbol)
        plan = self._plan

        # ---- arguments -------------------------------------------------
        if isinstance(args, dict):
            self.arg_dict = {k: self._as_nd(v) for k, v in args.items()}
            missing = [a for a in plan.arg_names if a not in self.arg_dict]
            if missing:
                raise MXNetError("bind missing arguments: %s" % missing)
        else:
            args = list(args)
            if len(args) != len(plan.arg_names):
                raise MXNetError(
                    "bind expects %d args, got %d" % (len(plan.arg_names), len(args)))
            self.arg_dict = {n: self._as_nd(a) for n, a in zip(plan.arg_names, args)}
        self.arg_arrays = [self.arg_dict[n] for n in plan.arg_names]

        # ---- gradients -------------------------------------------------
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in plan.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(plan.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in plan.arg_names}
        # inputs an op declares non-differentiable (labels, indices)
        for n in plan.nodes:
            if n.is_variable or not n.op.no_grad_inputs:
                continue
            in_names = n.op.input_names(n.attrs)
            for iname, (p, _) in zip(in_names, n.inputs):
                if iname in n.op.no_grad_inputs and p.is_variable:
                    self._grad_req[p.name] = "null"
        if args_grad is None:
            self.grad_dict = {}
        elif isinstance(args_grad, dict):
            self.grad_dict = {k: self._as_nd(v) for k, v in args_grad.items()}
        else:
            self.grad_dict = {
                n: self._as_nd(g) for n, g in zip(plan.arg_names, args_grad)
                if g is not None}
        for name in list(self.grad_dict):
            if self._grad_req.get(name, "null") == "null":
                del self.grad_dict[name]
        self.grad_arrays = [self.grad_dict.get(n) for n in plan.arg_names]

        # ---- aux states ------------------------------------------------
        if aux_states is None:
            aux_states = []
        if isinstance(aux_states, dict):
            self.aux_dict = {k: self._as_nd(v) for k, v in aux_states.items()}
        else:
            aux_states = list(aux_states)
            self.aux_dict = {n: self._as_nd(a)
                             for n, a in zip(plan.aux_names, aux_states)}
        for aname in plan.aux_names:
            if aname not in self.aux_dict:
                raise MXNetError("bind missing auxiliary state %r" % aname)
        self.aux_arrays = [self.aux_dict[n] for n in plan.aux_names]

        self._output_arrays: List = []
        self._monitor_callback = None
        self._jit_cache: Dict[Any, Any] = {}
        # compile-cache entry label for this executor's forwards ("fwd" by
        # default); specialized call sites (the generation decode step sets
        # "gen-step", its prefill "gen-prefill") override it so their
        # entries are both distinctly keyed and legible in
        # `compile_cache_admin.py ls`
        self._cache_kind = "fwd"
        # name of this executor's jitted forward as a device trace's
        # ``XLA Modules`` line shows it (``jit_<name>``); the generation
        # engine names its programs ``decode_b<lanes>`` / ``prefill_L<len>``
        self._program_name = "forward"
        # carried arguments (set_carried): argument name -> index of the
        # output that is its next value
        self._carried: Dict[str, int] = {}
        # whether the forward last built donates them (None: none built)
        self.carry_donated: Optional[bool] = None
        # NaiveEngine parity: MXNET_ENGINE_TYPE=NaiveEngine disables jit and
        # synchronizes after every call (threaded_engine.h:329-337 debugging).
        self._naive = env("MXNET_ENGINE_TYPE") == "NaiveEngine"
        # model parallelism: ctx-group → device placement compiled into the
        # step (group2ctx was previously accepted but silently ignored)
        self._placement = plan.placement_map(self._group2ctx)
        # SPMD shardings (set_shardings): mesh + per-name PartitionSpecs.
        # XLA partitions every compiled step from the committed input
        # shardings — tensor parallelism needs no graph changes here.
        self._shard_mesh = None
        # (mesh, batch axis) of a data-parallel bind: Pallas kernels run
        # per batch shard (set by DataParallelExecutorGroup)
        self._kernel_mesh = (None, None)
        self._shard_specs: Dict[str, Any] = {}
        self._shard_fingerprint = None

    # ------------------------------------------------------------------
    def _as_nd(self, v):
        from . import ndarray as nd

        if isinstance(v, nd.NDArray):
            return v
        return nd.array(v, self._ctx)

    @property
    def outputs(self) -> List:
        return self._output_arrays

    @property
    def output_dict(self) -> Dict[str, Any]:
        return dict(zip(self._plan.output_names, self._output_arrays))

    # ------------------------------------------------------------------
    # compiled callables
    # ------------------------------------------------------------------
    def _cast_fn(self):
        """Build the traced mixed-precision cast over an args dict."""
        if self._compute_dtype is None:
            return lambda args: args
        import jax.numpy as jnp

        cdt = jnp.dtype(self._compute_dtype)
        exclude = self._cast_exclude

        def cast(args):
            out = {}
            for k, v in args.items():
                if k not in exclude and v.dtype == jnp.float32:
                    out[k] = v.astype(cdt)
                else:
                    out[k] = v
            return out

        return cast

    def _bound(self, fn):
        """``fn`` with its Pallas kernels bound to this executor's device:
        compiled on a tpu context, interpreted (and saying so) on a cpu
        one — whatever the process's default backend — and, on a device
        mesh, run per batch shard (ops/interpret.py)."""
        from .ops.interpret import bind

        return bind(fn, self._ctx.jax_device().platform, *self._kernel_mesh)

    def _first_call(self, key, program, kind):
        """``program`` as it first goes into ``_jit_cache[key]``: its first
        call is a ``start:program`` span and puts the program itself
        there (profiler.first_call)."""
        return _prof.first_call(
            program, kind, functools.partial(self._jit_cache.__setitem__, key))

    def set_carried(self, carried: Dict[str, int]):
        """Declare arguments the forward carries: ``{argument name: index
        of the output that is its next value}`` (a KV pool plane and the
        ``k_pool_out`` the step makes of it).  Such a forward takes them
        as an argument of their own, donated so that the program updates
        them in place, and rebinds each to its output when it returns:
        the bound NDArray then holds the new value, the old buffer is
        dead, and ``outputs`` lists the bound NDArray itself at that
        index.  Executors that bind the same NDArray share the value."""
        outs = len(self._plan.output_entries)
        for name, idx in carried.items():
            if name not in self.arg_dict or not 0 <= int(idx) < outs:
                raise MXNetError("set_carried: no argument %r or no output "
                                 "%r" % (name, idx))
        self._carried = {k: int(v) for k, v in carried.items()}

    def _get_fwd(self, is_train: bool, internals: bool = False):
        import jax

        kind = self._cache_kind
        key = (kind, is_train, internals)
        if key not in self._jit_cache:
            plan = self._plan

            placement = self._placement
            cast = self._cast_fn()

            def fn(args, aux, rng):
                return plan.run(cast(args), aux, rng, is_train,
                                want_internals=internals, placement=placement)

            if self._carried:
                run, names = fn, self._carried_names()

                def fn(carried, args, aux, rng):  # noqa: F811
                    return run({**args, **dict(zip(names, carried))}, aux,
                               rng)

            fn.__name__ = self._program_name
            if self._naive:
                self._jit_cache[key] = self._bound(fn)
            else:
                from . import compile_cache as _cc
                from .ops.interpret import carrying

                jit_kw, static_key = {}, key
                if self._carried:
                    # as the fused step: an executable that may be
                    # serialized is built without donation (see
                    # _get_fused_step), and donation, which changes the
                    # compiled program, is part of the persistent key
                    donate = () if _cc.active() else (0,)
                    self.carry_donated = bool(donate)
                    jit_kw = {"donate_argnums": donate}
                    static_key = key + (("donate", donate),)
                    # the kernels that take a plane where it lies read this
                    fn = carrying(fn, self.carry_donated)
                fn = self._bound(fn)
                self._jit_cache[key] = self._first_call(key, _cc.maybe_cached(
                    jax.jit(fn, **jit_kw), kind, static_key, self), kind)
        return self._jit_cache[key]

    def _get_fwd_bwd(self, is_train: bool, diff_names: tuple, add_names: tuple):
        import jax

        key = ("fwdbwd", is_train, diff_names, add_names)
        if key not in self._jit_cache:
            plan = self._plan
            remat = bool(env("MXNET_BACKWARD_DO_MIRROR", 0, int))
            placement = self._placement

            cast = self._cast_fn()

            def forward_backward(diff_args, other_args, aux, rng, out_grads,
                                 old_grads):
                def f(d):
                    merged = dict(other_args)
                    merged.update(d)
                    outs, new_aux = plan.run(cast(merged), aux, rng, is_train,
                                             placement=placement)
                    return tuple(outs), new_aux

                f2 = jax.checkpoint(f) if remat else f
                primals, vjp_fn = jax.vjp(f2, diff_args)
                outs, new_aux = primals
                cts = tuple(
                    og if og is not None else jax.numpy.ones_like(o)
                    for o, og in zip(outs, out_grads))
                (grads,) = vjp_fn((cts, jax.tree_util.tree_map(
                    jax.numpy.zeros_like, new_aux)))
                for name in add_names:
                    grads[name] = grads[name] + old_grads[name]
                return list(outs), new_aux, grads

            fn = self._bound(forward_backward)
            if self._naive:
                self._jit_cache[key] = fn
            else:
                from . import compile_cache as _cc

                self._jit_cache[key] = self._first_call(key, _cc.maybe_cached(
                    jax.jit(fn), "fwdbwd", key, self), "fwdbwd")
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    # fused train step (forward + backward + optimizer update)
    # ------------------------------------------------------------------
    @staticmethod
    def _unwrap_state(state):
        """Optimizer state (NDArray / tuple / None) → jax pytree."""
        from . import ndarray as nd

        if state is None:
            return None
        if isinstance(state, nd.NDArray):
            return state._data
        if isinstance(state, (list, tuple)):
            return tuple(Executor._unwrap_state(s) for s in state)
        return state

    @staticmethod
    def _rewrap_state(holder, new, ctx):
        """Write a new jax pytree back into the Updater's NDArray structure
        (buffer rebinding only — no device work)."""
        from . import ndarray as nd

        if holder is None or new is None:
            return holder if new is None else nd.NDArray(new, ctx)
        if isinstance(holder, nd.NDArray):
            holder._set(new)
            return holder
        if isinstance(holder, (list, tuple)):
            return tuple(Executor._rewrap_state(h, n, ctx)
                         for h, n in zip(holder, new))
        return new

    def _fused_shardings(self, diff_args, states, aux, other_args):
        """(in_shardings, out_shardings) pytrees for the fused step when a
        mesh is active: every named array pins its PartitionSpec, optimizer
        state leaves inherit their parameter's spec when like-shaped (else
        replicate), and the rng/scalar slots stay unconstrained.  Lowering
        the step under explicit shardings (rather than inferring from the
        committed inputs alone) makes the SPMD layout part of the program
        signature — reshard bugs fail at compile, not as silent copies."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self._shard_mesh
        rep = NamedSharding(mesh, PartitionSpec())

        def ns(name):
            return NamedSharding(mesh,
                                 self._shard_specs.get(name, PartitionSpec()))

        def state_ns(name, sub):
            pshape = tuple(self.arg_dict[name].shape)

            def leaf(x):
                return ns(name) if tuple(x.shape) == pshape else rep

            return jax.tree_util.tree_map(leaf, sub)

        d = {k: ns(k) for k in diff_args}
        s = {k: state_ns(k, sub) for k, sub in states.items()}
        a = {k: ns(k) for k in aux}
        o = {k: ns(k) for k in other_args}
        in_s = (d, s, a, o, None, rep, None)
        # fifth slot: the step-guard verdict (ok, gnorm) — replicated scalars
        out_s = (None, a, d, s, rep)
        return in_s, out_s

    def _get_fused_step(self, key, update_infos, pure_update, needs_rng,
                        shardings=None, stable_key=None, guard=False):
        """Jitted forward+backward+update with donated param/state/aux
        buffers.  This is the whole of the reference's per-batch engine
        traffic (GraphExecutor::Forward/Backward + the kvstore push/pull +
        fused optimizer kernels, model.py:88-116) as ONE XLA program — no
        host dispatch per parameter, buffers reused in place via donation.
        Under an active mesh, ``shardings`` = (in_shardings, out_shardings)
        lowers the single program SPMD-partitioned.

        With ``guard`` (the training guardian's step guard) the program
        also reduces ``isfinite`` over every gradient and output and
        gates the param/state/aux update on the verdict: a non-finite
        step is SKIPPED on device (old buffers selected) and the scalar
        verdict comes back as a fifth result — one fused all-reduce, no
        extra host round-trip.  Guard off returns a constant-true
        verdict, which XLA folds away."""
        import jax
        import jax.numpy as jnp

        if key not in self._jit_cache:
            plan = self._plan
            placement = self._placement
            remat = bool(env("MXNET_BACKWARD_DO_MIRROR", 0, int))
            cast = self._cast_fn()

            def make_fn():
                def fused_step(diff_args, states, aux, other_args, rng, sc,
                               opt_rng):
                    lr0, wd0, t = sc

                    def f(d):
                        merged = dict(other_args)
                        merged.update(d)
                        outs, new_aux = plan.run(cast(merged), aux, rng,
                                                 True, placement=placement)
                        return tuple(outs), new_aux

                    f2 = jax.checkpoint(f) if remat else f
                    primals, vjp_fn = jax.vjp(f2, diff_args)
                    outs, new_aux = primals
                    cts = tuple(jnp.ones_like(o) for o in outs)
                    (grads,) = vjp_fn((cts, jax.tree_util.tree_map(
                        jnp.zeros_like, new_aux)))
                    keys = {}
                    if needs_rng and opt_rng is not None:
                        subkeys = jax.random.split(opt_rng, len(update_infos))
                        keys = {name: subkeys[i]
                                for i, (name, _, _, _)
                                in enumerate(update_infos)}
                    new_params = {}
                    new_states = {}
                    with jax.named_scope("optimizer"):
                        for name, _idx, lmult, wmult in update_infos:
                            w, s = pure_update(
                                diff_args[name], grads[name], states[name],
                                lr0 * lmult, wd0 * wmult, t, keys.get(name))
                            new_params[name] = w
                            new_states[name] = s
                    if guard:
                        with jax.named_scope("guard"):
                            ok = jnp.bool_(True)
                            sq = jnp.float32(0)
                            for name, _idx, _, _ in update_infos:
                                g = grads[name]
                                ok &= jnp.all(jnp.isfinite(g))
                                sq += jnp.sum(jnp.square(
                                    g.astype(jnp.float32)))
                            for o in outs:
                                if jnp.issubdtype(o.dtype, jnp.floating):
                                    ok &= jnp.all(jnp.isfinite(o))
                            gnorm = jnp.sqrt(sq)
                            # the f32 norm overflowing is itself an
                            # anomaly: a single exponent bit-flip lands
                            # ~1e38 in a gradient, which is finite but
                            # squares to inf — catch it here, not N steps
                            # later in the spike detector
                            ok &= jnp.isfinite(gnorm)
                        # on-device skip: a poisoned batch leaves params,
                        # optimizer state and aux (BN stats) untouched
                        sel = lambda new, old: jnp.where(ok, new, old)
                        new_params = {k: sel(v, diff_args[k])
                                      for k, v in new_params.items()}
                        new_states = jax.tree_util.tree_map(
                            sel, new_states, states)
                        new_aux = jax.tree_util.tree_map(sel, new_aux, aux)
                    else:
                        ok = jnp.bool_(True)
                        gnorm = jnp.float32(0)
                    return (list(outs), new_aux, new_params, new_states,
                            (ok, gnorm))

                return self._bound(fused_step)

            if self._naive:
                self._jit_cache[key] = make_fn()
            else:
                from . import compile_cache as _cc

                # Cache-eligible executables are built WITHOUT donation:
                # XLA's executable deserializer can mis-bind donated
                # (input-output aliased) arguments that share a shape, so
                # an entry compiled here must stay correct when another
                # process deserializes it.  The default (cache off) keeps
                # in-place buffer reuse.
                donate_allowed = not _cc.active()
                # the mesh this executor was given decides what the step
                # is compiled with: data-parallel over several TPUs, gradient
                # all-reduces that run under the backward pass; a step that
                # partition rules shard (_shard_mesh) is compiled as before
                from .sharding import collective_compiler_options

                options = {} if self._shard_mesh is not None else \
                    collective_compiler_options(self._kernel_mesh[0])

                donate = (0, 1, 2) if donate_allowed else ()
                kw = {"compiler_options": options} if options else {}
                if shardings is not None:
                    kw.update(in_shardings=shardings[0],
                              out_shardings=shardings[1])
                jfn = jax.jit(make_fn(), donate_argnums=donate, **kw)
                # the persistent key uses stable_key (no object ids) so a
                # fresh process — or a fresh optimizer instance with the
                # same hypers — maps to the same disk entry; donation, remat
                # and the compiler's options change the compiled program,
                # so they are part of the key (a step with no options keeps
                # the key it had)
                if stable_key is not None:
                    stable_key = stable_key + (("donate", donate),
                                               ("remat", int(remat)))
                    if options:
                        stable_key += (("compiler_options",
                                        tuple(sorted(options.items()))),)
                self._jit_cache[key] = self._first_call(key, _cc.maybe_cached(
                    jfn, "fused", stable_key, self), "fused")
        return self._jit_cache[key]

    def fused_step(self, optimizer, updater, param_names):
        """Run one fused train step: loads nothing (inputs must already be in
        ``arg_dict``), updates params/states/aux in place, sets outputs.

        ``param_names`` gives the updater index space (position in list ==
        kvstore key, as Module wires idx2name).  Requires every param's
        grad_req to be 'write' or 'null' and an optimizer with
        ``pure_update``.

        Three spans, siblings on the caller's thread: ``:pack`` (building
        the call), the dispatch itself, ``:rebind`` (the results back into
        the bound arrays)."""
        from . import ndarray as nd

        with _prof.Frame("Executor.fused_step:pack", "exec"):
            fn, call_args, infos, guard, first_build = self._fused_pack(
                optimizer, updater, param_names)
        with _prof.Frame("Executor.fused_step", "exec"):
            outs, new_aux, new_params, new_states, verdict = fn(*call_args)
        # the on-device (ok, grad_norm) verdict: still device scalars —
        # the guardian reads them where the step already syncs (metric
        # update), so the guard adds no host round-trip of its own
        self._guard_verdict = verdict if guard else None
        if first_build and not self._naive:
            # when the compile cache primed this executable, XLA's cost
            # analysis and, over a mesh, its count of collectives rode along
            # (entry meta on hits, read once from the fresh Compiled on
            # misses) — StepMonitor consumes these instead of
            # re-lowering+re-compiling the program
            self._fused_cost_info = getattr(fn, "cost_info", None)
            self._fused_collectives = getattr(fn, "collectives", None)

        with _prof.Frame("Executor.fused_step:rebind", "exec"):
            for name, idx, _, _ in infos:
                self.arg_dict[name]._set(new_params[name])
                updater.states[idx] = self._rewrap_state(
                    updater.states[idx], new_states[name], self._ctx)
            for k, v in new_aux.items():
                self.aux_dict[k]._set(v)
            self._output_arrays = [nd.NDArray(o, self._ctx) for o in outs]
        if self._naive:
            for o in self._output_arrays:
                o.wait_to_read()
        return self._output_arrays

    def _fused_pack(self, optimizer, updater, param_names):
        """The host's work before the fused step's dispatch: optimizer
        bookkeeping, the argument trees, the program's cache lookup.
        Returns (program, its arguments, update infos, guard, whether the
        program was built by this call)."""
        import numpy as _np
        from . import random as _random

        plan = self._plan
        infos = []
        for idx, name in enumerate(param_names):
            if self._grad_req.get(name, "null") == "null":
                continue
            if idx not in updater.states:
                updater.states[idx] = optimizer.create_state(
                    idx, self.arg_dict[name])
            # static per-param multipliers (scheduler lr stays traced)
            lmult = optimizer.lr_mult.get(idx, optimizer.lr_mult.get(
                optimizer.idx2name.get(idx, name), 1.0))
            wmult = optimizer.wd_mult.get(idx, optimizer.wd_mult.get(
                optimizer.idx2name.get(idx, name), 1.0))
            infos.append((name, idx, float(lmult), float(wmult)))
            optimizer._update_count(idx)

        t = optimizer.num_update
        lr0 = optimizer.lr_scheduler(t) if optimizer.lr_scheduler is not None \
            else optimizer.lr
        from . import guardian as _guardian

        if _guardian._governor is not None:
            # re-warm ramp: lr rides in as a traced scalar, so the ramp
            # never recompiles the fused program
            lr0 *= _guardian.current_lr_mult()
        sc = (_np.float32(lr0), _np.float32(optimizer.wd), _np.int32(t))

        diff_args = {}
        states = {}
        other_args = {}
        diff_set = {name for name, _, _, _ in infos}
        for k, v in self.arg_dict.items():
            (diff_args if k in diff_set else other_args)[k] = v._data
        for name, idx, _, _ in infos:
            states[name] = self._unwrap_state(updater.states[idx])
        aux = {k: v._data for k, v in self.aux_dict.items()}

        # donation requires distinct buffers; NDArray.copy() shares the
        # immutable jax array (e.g. DCASGD's previous-weight state right
        # after create_state), so break aliases with a real copy once
        import jax

        seen = {id(v) for v in diff_args.values()}

        def _dedupe(leaf):
            if leaf is None:
                return None
            if id(leaf) in seen:
                return jax.numpy.array(leaf, copy=True)
            seen.add(id(leaf))
            return leaf

        states = jax.tree_util.tree_map(_dedupe, states)
        aux = {k: _dedupe(v) for k, v in aux.items()}
        rng = _random.next_key() if plan.stochastic_nodes else None
        opt_rng = _random.next_key() if optimizer.needs_rng else None

        # hyperparameters are baked into the trace, so fingerprint every
        # scalar hyper (momentum, betas, rho, ...) — not just identity —
        # excluding per-step bookkeeping and the traced lr/wd scalars
        hypers = tuple(sorted(
            (k, float(v)) for k, v in vars(optimizer).items()
            if isinstance(v, (int, float, bool)) and
            k not in ("num_update", "begin_num_update", "lr", "wd")))
        # the guardian's step guard changes the compiled program (isfinite
        # reduction + gated update), so it discriminates both cache keys
        from . import guardian as _guardian

        guard = _guardian.enabled()
        key = ("fused", tuple(infos), id(optimizer), type(optimizer).__name__,
               hypers, float(optimizer.rescale_grad),
               float(optimizer.clip_gradient or 0.0),
               self._shard_fingerprint, guard)
        # the same key with every process-unstable part (object ids, shard
        # fingerprint — the compile cache derives a stable one from the
        # mesh itself) removed: what the persistent compile cache keys on
        stable_key = ("fused", tuple(infos), type(optimizer).__name__,
                      hypers, float(optimizer.rescale_grad),
                      float(optimizer.clip_gradient or 0.0),
                      bool(optimizer.needs_rng), ("guard", int(guard)))
        first_build = key not in self._jit_cache
        shardings = None
        abstract_args = None
        if first_build and not self._naive:
            if self._shard_mesh is not None:
                shardings = self._fused_shardings(diff_args, states, aux,
                                                  other_args)
            # abstract arg signature of the fused call: perf_probe reuses
            # it (via _fused_introspect) to lower the exact same program
            # (committed arrays keep their sharding: on a device mesh the
            # partitioning of the program comes from nothing else)
            abstract_args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if getattr(x, "committed", False)
                    else None),
                (diff_args, states, aux, other_args, rng, sc, opt_rng))
        fn = self._get_fused_step(key, tuple(infos), optimizer.pure_update,
                                  optimizer.needs_rng, shardings,
                                  stable_key=stable_key, guard=guard)
        if first_build and not self._naive:
            # introspection hook (compile-miss path only — zero per-step
            # cost), so tools/perf_probe.py can lower/compile the exact
            # same program and read XLA cost analysis / HLO without
            # re-deriving the arg packing
            self._fused_introspect = (fn, abstract_args)
            # consumed by telemetry.StepMonitor (Module.update): one XLA
            # cost analysis per new executable, never per step
            self._fused_new_compile = True
        return (fn, (diff_args, states, aux, other_args, rng, sc, opt_rng),
                infos, guard, first_build)

    def fused_op_scopes(self):
        """{instruction name: scope path} of the compiled fused step: the
        way from a device trace's ``fusion.674`` to the Symbol node (or
        ``optimizer`` / ``guard``) it came from.  Compiles the program the
        last ``fused_step`` ran once more (``_fused_introspect``)."""
        from .hlo_analysis import op_scopes

        fn, abstract = self._fused_introspect
        return op_scopes(fn.lower(*abstract).compile().as_text())

    # ------------------------------------------------------------------
    # execution API
    # ------------------------------------------------------------------
    def set_shardings(self, mesh, arg_specs=None, aux_specs=None):
        """Tensor/data-parallel placement through the product executor.

        ``mesh`` is a ``jax.sharding.Mesh``; ``arg_specs``/``aux_specs`` map
        argument/aux names to ``PartitionSpec``s (unnamed arrays are
        replicated).  Every bound arg, gradient buffer and aux state is
        committed onto the mesh; XLA then partitions each compiled step
        (forward / backward / fused) over it, inserting the collectives —
        e.g. a FullyConnected weight sharded on a 'model' axis runs as a
        partitioned matmul with the activation all-gather/psum compiled in.
        TPU-native replacement for the reference's multi-device executor
        split (graph_executor.cc device placement + kvstore comm); batch
        inputs fed later via ``forward(**kwargs)`` keep their spec."""
        from jax.sharding import PartitionSpec

        self._shard_mesh = mesh
        self._shard_specs = dict(arg_specs or {})
        if aux_specs:
            self._shard_specs.update(aux_specs)
        # jit-cache discriminator: a later set_shardings with different
        # specs must re-lower the fused step instead of reusing a program
        # compiled for the old layout
        self._shard_fingerprint = (
            id(mesh), tuple(sorted((k, str(v))
                                   for k, v in self._shard_specs.items())))

        known = set(self.arg_dict) | set(self.aux_dict) | set(self.grad_dict)
        unknown = sorted(set(self._shard_specs) - known)
        if unknown:
            raise MXNetError(
                "set_shardings: specs name no bound argument/aux: %s"
                % unknown)

        from .sharding import place as _place

        def put(arrs):
            for name, arr in arrs.items():
                spec = self._shard_specs.get(name, PartitionSpec())
                arr._set(_place(arr._data, mesh, spec))

        put(self.arg_dict)
        put(self.aux_dict)
        put(self.grad_dict)

    def _write_arg(self, name, value, aux=False):
        """The single write path for bound arrays: one host→device
        transfer, committed straight onto the mesh when shardings are
        active (so a caller-side update never silently drops a spec or
        double-copies the batch)."""
        from . import ndarray as nd

        target = (self.aux_dict if aux else self.arg_dict)[name]
        if self._shard_mesh is None:
            target[:] = value if not isinstance(value, np.ndarray) else \
                nd.array(value, self._ctx)
            return
        from jax.sharding import PartitionSpec

        from .sharding import place as _place

        v = value._data if isinstance(value, nd.NDArray) else \
            np.asarray(value, dtype=target.dtype)
        spec = self._shard_specs.get(name, PartitionSpec())
        target._set(_place(v, self._shard_mesh, spec))

    def _forward_args(self, rng):
        """The arguments of the forward program (``_get_fwd``): ``(args,
        aux, rng)``, with the carried arguments split off in front when
        there are any."""
        args = {k: v._data for k, v in self.arg_dict.items()}
        aux = {k: v._data for k, v in self.aux_dict.items()}
        if not self._carried:
            return args, aux, rng
        carried = tuple(args.pop(k) for k in self._carried_names())
        return carried, args, aux, rng

    def _carried_names(self):
        """The carried arguments in the order of their outputs.  JAX pairs
        a donated argument with the first unclaimed output of its shape
        and dtype: handed over in another order (a dict's: ``layer10_``
        sorts before ``layer1_``) equal-shaped planes are paired
        crosswise, and XLA copies each one whole to put the result into
        the buffer it was paired with."""
        return sorted(self._carried, key=self._carried.get)

    def forward(self, is_train: bool = False, **kwargs):
        from . import ndarray as nd

        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            self._write_arg(k, v)
        rng = _random.next_key() if self._plan.stochastic_nodes else None
        self._last_rng = rng
        call = self._forward_args(rng)
        with _prof.Frame("Executor.forward", "exec"):
            if self._monitor_callback is not None:
                outs, new_aux, internals = self._get_fwd(is_train, True)(
                    *call)
                for name, arr in internals.items():
                    self._monitor_callback(name, nd.NDArray(arr, self._ctx))
            else:
                outs, new_aux = self._get_fwd(is_train, False)(*call)
        if is_train:
            for k, v in new_aux.items():
                self.aux_dict[k]._set(v)
        self._output_arrays = [nd.NDArray(o, self._ctx) for o in outs]
        for name, idx in self._carried.items():
            self.arg_dict[name]._set(outs[idx])
            self._output_arrays[idx] = self.arg_dict[name]
        if self._naive:
            for o in self._output_arrays:
                o.wait_to_read()
        return self._output_arrays

    def backward(self, out_grads=None, is_train: bool = True):
        self._forward_backward(out_grads, is_train=is_train, update_aux=False)

    def forward_backward(self, out_grads=None, is_train: bool = True, **kwargs):
        """Fused train step (one XLA program): forward + grads + aux update.
        The hot path used by Module.fit."""
        from . import ndarray as nd

        for k, v in kwargs.items():
            self._write_arg(k, v)
        self._last_rng = _random.next_key() if self._plan.stochastic_nodes else None
        self._forward_backward(out_grads, is_train=is_train, update_aux=True,
                               set_outputs=True)
        return self._output_arrays

    def _forward_backward(self, out_grads, is_train: bool, update_aux: bool,
                          set_outputs: bool = False):
        from . import ndarray as nd

        plan = self._plan
        diff_names = tuple(sorted(
            n for n in plan.arg_names if self._grad_req.get(n, "null") != "null"))
        if not diff_names:
            if set_outputs:
                self.forward(is_train=is_train)
            return
        add_names = tuple(sorted(
            n for n in diff_names if self._grad_req[n] == "add"))
        # grad_req='add' accumulates into the existing gradient array; if the
        # user bound none, start the accumulator at zero instead of failing
        # with a KeyError inside the traced function.
        for name in add_names:
            if name not in self.grad_dict:
                src = self.arg_dict[name]
                self.grad_dict[name] = nd.zeros(src.shape, self._ctx,
                                                dtype=src.dtype)
        args = {k: v._data for k, v in self.arg_dict.items()}
        aux = {k: v._data for k, v in self.aux_dict.items()}
        diff_args = {k: args[k] for k in diff_names}
        other_args = {k: v for k, v in args.items() if k not in diff_names}
        rng = getattr(self, "_last_rng", None)
        if rng is None and plan.stochastic_nodes:
            rng = _random.next_key()
        if out_grads is None:
            ogs = [None] * len(plan.output_entries)
        elif isinstance(out_grads, (list, tuple)):
            ogs = [g._data if isinstance(g, nd.NDArray) else g for g in out_grads]
        else:
            ogs = [out_grads._data if isinstance(out_grads, nd.NDArray) else out_grads]
        old_grads = {k: self.grad_dict[k]._data for k in add_names
                     if k in self.grad_dict}
        fn = self._get_fwd_bwd(is_train, diff_names, add_names)
        with _prof.Frame("Executor.forward_backward", "exec"):
            outs, new_aux, grads = fn(diff_args, other_args, aux, rng, ogs,
                                      old_grads)
        for name in diff_names:
            if name in self.grad_dict:
                self.grad_dict[name]._set(grads[name])
            else:
                self.grad_dict[name] = nd.NDArray(grads[name], self._ctx)
        self.grad_arrays = [self.grad_dict.get(n) for n in plan.arg_names]
        if update_aux:
            for k, v in new_aux.items():
                self.aux_dict[k]._set(v)
        if set_outputs:
            self._output_arrays = [nd.NDArray(o, self._ctx) for o in outs]
        if self._naive:
            for g in self.grad_dict.values():
                g.wait_to_read()

    # ------------------------------------------------------------------
    # parameter management
    # ------------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self._write_arg(name, arr)
            elif not allow_extra_params:
                raise MXNetError("Found name \"%s\" not in arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self._write_arg(name, arr, aux=True)
                elif not allow_extra_params:
                    raise MXNetError("Found name \"%s\" not in aux states" % name)

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new input shapes (sharing the plan;
        XLA compile cache keyed by shapes plays the role of the reference's
        shared memory pool, graph_executor.cc:483-529)."""
        from . import ndarray as nd

        new_shapes = dict(kwargs)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**new_shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes for reshape")
        args = {}
        for name, shape in zip(self._plan.arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if tuple(cur.shape) == tuple(shape):
                args[name] = cur
            else:
                args[name] = nd.zeros(shape, self._ctx, dtype=cur.dtype)
        aux = {}
        for name, shape in zip(self._plan.aux_names, aux_shapes):
            cur = self.aux_dict[name]
            aux[name] = cur if tuple(cur.shape) == tuple(shape) else \
                nd.zeros(shape, self._ctx, dtype=cur.dtype)
        grads = {n: nd.zeros(args[n].shape, self._ctx, dtype=args[n].dtype)
                 for n in self.grad_dict}
        return Executor(self._symbol, self._ctx, args, grads or None,
                        self._grad_req, aux, group2ctx=self._group2ctx,
                        shared_exec=self)

    def debug_str(self) -> str:
        lines = ["Symbol outputs: %s" % ", ".join(self._plan.output_names)]
        for n in self._plan.nodes:
            if n.is_variable:
                lines.append("Variable:%s" % n.name)
            else:
                lines.append("Op:%s, Name=%s" % (n.op.name, n.name))
        total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in self.arg_dict.values())
        lines.append("Total %d MB allocated for args" % (total >> 20))
        return "\n".join(lines)
