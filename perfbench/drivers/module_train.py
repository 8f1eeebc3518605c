"""Training cells: the ``Module`` fused step, driven as ``fit()`` drives it.

Set-up builds ONE module (the compiled step with its state), sets the seeded
weights, drives it through its first steps on rows that all differ, and hands
that same object to the window.  The window dispatches whole steps for
``--seconds`` and ends in one blocking read; the rate is the items of the
steps dispatched over the time between the two reads.  Afterwards, with the
module freed, the plain reference follows the same first steps.
"""
import collections
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.harness import check as _check
from perfbench.harness import trace as _trace
from perfbench.models.precision import leaf_norms, leaf_slices, seed_key


@jax.jit
def _leaf_reading(state):
    """Norm and strided slice of one leaf of the optimizer's state."""
    return leaf_norms({"x": state})["x"], leaf_slices({"x": state})["x"]


def first_gradient(mod, optimizer, opt_params):
    """Norm and slice of the first gradient as the optimizer got it, per
    parameter, worked out from the optimizer's state after one step (zero
    state before it): (norms, slices on the host).  Leaf by leaf and with no
    copy of the gradient: set-up may not need more memory than the window,
    or the runtime gives up the step's reserved scratch and every step of
    the window pays for it (PERF.md, Findings, PR 24)."""
    if optimizer == "adam":
        scale = 1.0 / (1.0 - float(opt_params.get("beta1", 0.9)))
        moment = lambda st: st[0]._data
    elif optimizer == "sgd":
        scale = -1.0 / float(opt_params["learning_rate"])
        moment = lambda st: st._data
    else:
        raise ValueError("no rule to read the first gradient from the "
                         "state of optimizer %r" % optimizer)
    norms, slices = {}, {}
    for idx, name in enumerate(mod._exec_group.param_names):
        st = mod._updater.states.get(idx)
        if st is None:
            continue
        norm, part = _leaf_reading(moment(st))
        norms[name] = abs(scale) * float(norm)
        slices[name] = np.float32(scale) * np.asarray(part)
    return norms, slices


class Trainer:
    """The one object set-up builds and the window drives."""

    def __init__(self, h):
        import mxnet_tpu as mx

        self.h = h
        cfg, mix, b = h.config, h.mix, h.builder
        self.layers = b.train_layers(cfg)
        self.chips = h.chips
        self.global_batch = int(mix["batch_per_chip"]) * h.chips
        self.items = b.items_per_batch(mix, self.global_batch)
        contexts = [mx.tpu(i) for i in range(h.chips)]
        h.mark("imports")
        weights = jax.block_until_ready(
            h.family.make_weights(cfg, h.seed, self.layers))
        h.mark("seeded weights")
        arg, aux = b.program_params(weights)
        sym = b.train_symbol(mx, cfg, mix, self.layers)
        data_descs, label_descs = b.train_descs(mx, cfg, mix,
                                                self.global_batch)
        self.mod = mx.mod.Module(sym, label_names=(b.LABEL,),
                                 context=contexts,
                                 compute_dtype=mix.get("compute_dtype"))
        self.mod.bind(data_shapes=data_descs, label_shapes=label_descs,
                      for_training=True)
        ctx0 = contexts[0]
        self.mod.init_params(
            arg_params={k: mx.nd.NDArray(v, ctx0) for k, v in arg.items()},
            aux_params={k: mx.nd.NDArray(v, ctx0) for k, v in aux.items()})
        del weights, arg, aux
        h.mark("module bound, parameters set")
        self.opt_params = dict(mix["optimizer_params"])
        if mix.get("rescale_by_items", True):
            self.opt_params["rescale_grad"] = 1.0 / self.items
        self.mod.init_optimizer(kvstore="local", optimizer=mix["optimizer"],
                                optimizer_params=self.opt_params)
        key = seed_key(h.seed)
        self.batches, self.ref_batches = [], []
        for i in range(int(mix["pool"])):
            d, l, rd, rl = b.make_batch(cfg, mix,
                                        jax.random.fold_in(key, 1000 + i),
                                        self.global_batch)
            self.batches.append(mx.io.DataBatch(
                data=[mx.nd.NDArray(d, ctx0)],
                label=[mx.nd.NDArray(l, ctx0)], pad=0))
            self.ref_batches.append((rd, rl))
        self.label_name = b.LABEL
        self.n = 0
        h.mark("optimizer, seeded batches")

        @jax.jit
        def nll(probs, lab):
            lab = lab.reshape(-1).astype(jnp.int32)
            p = jnp.take_along_axis(probs, lab[:, None], 1)
            return -jnp.mean(jnp.log(p.astype(jnp.float32)))

        self._nll = nll

    def step(self):
        """One whole training step through the module's own call and feed;
        returns the step's loss, still on the device."""
        batch = self.batches[self.n % len(self.batches)]
        self.n += 1
        self.mod.forward_backward(batch)
        self.mod.update()
        ex = self.mod._exec_group.execs[0]
        return self._nll(ex.outputs[0]._data,
                         ex.arg_dict[self.label_name]._data)

    def sync(self):
        """Blocking read of the last parameter: its value depends on every
        step before it."""
        name = self.mod._exec_group.param_names[-1]
        return self.mod._exec_group.execs[0].arg_dict[name].asnumpy()

    def params(self):
        ex = self.mod._exec_group.execs[0]
        return {n: ex.arg_dict[n]._data
                for n in self.mod._exec_group.param_names}

    def first_steps(self, steps):
        """Drive the module from the seed through its first steps; returns
        what the reference will be held against."""
        losses, gnorm, gslice = [], None, None
        for i in range(steps):
            losses.append(float(self.step()))
            self.h.mark("step %d" % (i + 1))
            if i == 0:
                gnorm, gslice = first_gradient(
                    self.mod, self.h.mix["optimizer"], self.opt_params)
        delta = self.h.family.delta_norms(self.h.config, self.layers,
                                          self.h.seed, self.params())
        self.h.mark("norms of gradient and change")
        return {"loss": losses, "grad_norm": gnorm, "grad_slice": gslice,
                "delta_norm": delta}

    def step_temp_bytes(self):
        """Scratch memory of the compiled step, which the device's
        allocator statistics leave out (they count arrays): from the
        executable the window ran, looked up again by its signature."""
        ex = self.mod._exec_group.execs[0]
        fn, abstract = ex._fused_introspect
        ma = fn.lower(*abstract).compile().memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes", 0) or 0)

    def free(self):
        self.mod = None
        self.batches = None
        gc.collect()


def reference_numbers(h, got, layers, ref_batches, prec="f32"):
    """The reference's (or, at a lower ``prec``, the control's) first steps
    on the rows the program was driven through."""
    return h.family.follow_training(
        h.config, layers, dict(h.mix["optimizer_params"]), h.seed,
        ref_batches, steps=len(got["loss"]), prec=prec, devices=h.devices())


def run(h):
    mix = h.mix
    tr = Trainer(h)
    got = tr.first_steps(int(mix["first_steps"]))
    for _ in range(int(mix.get("warm_steps", 1))):
        tr.step()
    tr.sync()
    h.mark("warm-up")
    for d in h.devices():
        st = d.memory_stats() or {}
        # reserved is the step's scratch: where set-up made the runtime give
        # it up, it reads less than the program's scratch here
        print("[memory] %s at the window's opening: in use %d, peak %d, "
              "reserved %d, largest free block %d"
              % (d, st.get("bytes_in_use", 0),
                 st.get("peak_bytes_in_use", 0), st.get("bytes_reserved", 0),
                 st.get("largest_free_block_bytes", 0)), flush=True)
    h.open_window()

    seconds = float(h.seconds)
    traced = seconds * float(mix.get("trace_share", 0.3)) if h.trace else 0.0
    in_flight = int(mix.get("in_flight", 2))
    pending, losses = collections.deque(), []
    n0 = tr.n
    t_a = time.perf_counter()
    while time.perf_counter() - t_a < seconds - traced:
        losses.append(tr.step())
        pending.append(losses[-1])
        if len(pending) > in_flight:
            pending.popleft().block_until_ready()
    tr.sync()
    t_b = time.perf_counter()
    steps = tr.n - n0
    rate = steps * tr.items / (t_b - t_a)
    print("[window] %d whole steps of %d items in %.4f s: %.4f items/s"
          % (steps, tr.items, t_b - t_a, rate), flush=True)

    step_ms, reduced = [], None
    if h.trace:
        tdir = h.trace_dir()
        _trace.start_trace(tdir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:window"):
            while time.perf_counter() - t0 < traced or len(step_ms) < 3:
                s0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench:step"):
                    losses.append(tr.step())
                    losses[-1].block_until_ready()
                step_ms.append((time.perf_counter() - s0) * 1e3)
        jax.profiler.stop_trace()
        reduced = _trace.reduce(_trace.load(_trace.find_xplane(tdir)))
    h.close_window()

    vals = [float(x) for x in losses]
    failed = sum(1 for v in vals if not math.isfinite(v))
    h.mark("window")
    peak = h.memory_peak_bytes(tr.step_temp_bytes())
    h.mark("memory reading")
    ref_batches, layers = tr.ref_batches, tr.layers
    tr.free()

    want = reference_numbers(h, got, layers, ref_batches)
    h.mark("reference")
    print("[check] losses program %s reference %s"
          % (["%.5f" % x for x in got["loss"]],
             ["%.5f" % x for x in want["loss"]]), flush=True)
    for name, value in _check.training_numbers(got, want).items():
        h.checks.add(name, value)
        h.readings[name] = value
    if h.control:
        low = reference_numbers(h, got, layers, ref_batches, h.control)
        for name, value in _check.training_numbers(low, want).items():
            h.readings["control_" + name] = value

    return {
        "attempted": len(vals), "failed": failed,
        "end_to_end": {mix["rate_metric"]: rate},
        "memory_peak_bytes": peak,
        "info": {"kind": "train", "rate": rate, "steps": steps,
                 "items_per_step": tr.items, "step_ms": step_ms,
                 "trace": reduced, "chips": h.chips,
                 "flops_per_item": h.builder.train_flops_per_item(
                     h.config, mix, layers),
                 "layers": layers, "batch_per_chip":
                     int(mix["batch_per_chip"])}}
