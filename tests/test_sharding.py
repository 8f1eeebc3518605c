"""GSPMD named-mesh partitioning (mxnet_tpu.sharding): mesh building,
regex rules -> PartitionSpec, placement helpers, and the sharded fused
train step on the 8-virtual-device CPU mesh — including 2-D
("data","model") tensor parallelism matching single-device training."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sharding
from mxnet_tpu.base import MXNetError


def P(*args):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*args)


# ----------------------------------------------------------------------
# mesh construction
# ----------------------------------------------------------------------
def test_build_mesh_infers_axis():
    mesh = sharding.build_mesh("data=-1,model=2")
    assert sharding.mesh_axes(mesh) == {"data": 4, "model": 2}
    assert mesh.axis_names == ("data", "model")


def test_build_mesh_forms():
    assert sharding.mesh_axes(sharding.build_mesh()) == {"data": 8}
    assert sharding.mesh_axes(sharding.build_mesh(
        (("model", 2), ("data", -1)))) == {"model": 2, "data": 4}
    assert sharding.mesh_axes(sharding.build_mesh(
        {"data": 2, "model": 4})) == {"data": 2, "model": 4}
    cfg = sharding.MeshConfig.parse("data=8")
    assert sharding.mesh_axes(sharding.build_mesh(cfg)) == {"data": 8}


def test_build_mesh_errors():
    with pytest.raises(MXNetError, match="duplicate"):
        sharding.MeshConfig(("data", 2), ("data", 4))
    with pytest.raises(MXNetError, match="at most one"):
        sharding.MeshConfig(("a", -1), ("b", -1))
    with pytest.raises(MXNetError, match="not divisible"):
        sharding.build_mesh("data=-1,model=3")
    with pytest.raises(MXNetError, match="covers"):
        sharding.build_mesh("data=2,model=2")
    with pytest.raises(MXNetError, match="name=size"):
        sharding.MeshConfig.parse("data:4")


# ----------------------------------------------------------------------
# rule matching
# ----------------------------------------------------------------------
def test_rule_matching_first_hit_wins_and_explain():
    rules = sharding.PartitionRules([
        (r"_weight$", P("model", None)),
        (r"fc1_weight$", P(None, "model")),  # shadowed by the rule above
        (r"_bias$", P()),
    ], fallback=P(), name="t")
    params = {"fc1_weight": (8, 4), "fc1_bias": (8,), "gamma": (4,),
              "scalar": ()}
    specs = rules.match(params)
    assert specs["fc1_weight"] == P("model", None)
    assert specs["fc1_bias"] == P()
    assert specs["gamma"] == P()        # fallback
    assert specs["scalar"] == P()       # scalar short-circuit

    rows = {r["param"]: r for r in rules.explain(params)}
    assert rows["fc1_weight"]["rule"] == r"_weight$"
    assert rows["gamma"]["rule"] == "<fallback>"
    assert rows["scalar"]["rule"] == "<scalar>"
    table = rules.explain_str(params)
    assert "fc1_weight" in table and "<fallback>" in table


def test_unmatched_param_raises_with_name():
    rules = sharding.PartitionRules([(r"_weight$", P("model", None))])
    with pytest.raises(MXNetError, match="mystery_param"):
        rules.match({"mystery_param": (4, 4)})


def test_match_partition_rules_functional_and_presets():
    specs = sharding.match_partition_rules(
        [(r"w$", P("data"))], {"w": (8,), "b": (4,)}, fallback=P())
    assert specs == {"w": P("data"), "b": P()}
    mega = sharding.get_preset("transformer_megatron")
    specs = mega.match({"layer0_qkv_weight": (96, 32),
                        "layer0_proj_weight": (32, 32),
                        "layer0_ln1_gamma": (32,),
                        "lm_head_weight": (64, 32)})
    assert specs["layer0_qkv_weight"] == P("model", None)
    assert specs["layer0_proj_weight"] == P(None, "model")
    assert specs["layer0_ln1_gamma"] == P()
    assert specs["lm_head_weight"] == P("model", None)
    with pytest.raises(MXNetError, match="unknown partition-rule preset"):
        sharding.get_preset("nope")


def test_validate_specs_rejects_uneven_split():
    mesh = sharding.build_mesh("data=4,model=2")
    with pytest.raises(MXNetError, match="w1.*not divisible"):
        sharding.validate_specs(mesh, {"w1": P(None, "model")},
                                {"w1": (4, 7)})
    with pytest.raises(MXNetError, match="not a mesh axis"):
        sharding.validate_specs(mesh, {"w1": P("pipeline")}, {"w1": (8, 8)})


# ----------------------------------------------------------------------
# placement
# ----------------------------------------------------------------------
def test_shard_and_gather_roundtrip():
    from jax.sharding import NamedSharding

    mesh = sharding.build_mesh("data=4,model=2")
    w = np.arange(64, dtype=np.float32).reshape(8, 8)
    placed = sharding.shard_params(
        {"w": mx.nd.array(w), "b": mx.nd.ones((3,))},
        mesh, {"w": P("model", None)})
    jw = placed["w"]._data
    assert jw.sharding.is_equivalent_to(
        NamedSharding(mesh, P("model", None)), 2)
    assert {tuple(s.data.shape) for s in jw.addressable_shards} == {(4, 8)}
    host = sharding.gather_params(placed)
    np.testing.assert_array_equal(host["w"], w)
    np.testing.assert_array_equal(host["b"], np.ones(3, np.float32))


def test_place_is_noop_for_already_placed():
    import jax
    from jax.sharding import NamedSharding

    mesh = sharding.build_mesh("data=8")
    x = jax.device_put(np.ones((8, 4), np.float32),
                       NamedSharding(mesh, P("data", None)))
    assert sharding.place(x, mesh, P("data", None)) is x


def test_place_passes_through_equivalent_cross_process_stub():
    # single-process runs cannot create a real cross-process array, so a
    # duck-typed stand-in checks the no-op branch: an array that is NOT
    # fully addressable but already carries the target sharding must pass
    # through untouched instead of raising
    from jax.sharding import NamedSharding

    mesh = sharding.build_mesh("data=8")
    target = NamedSharding(mesh, P())

    class Stub:
        sharding = target
        committed = True
        ndim = 2
        shape = (4, 4)
        is_fully_addressable = False
        is_fully_replicated = False

    stub = Stub()
    assert sharding.place(stub, mesh, P()) is stub


def test_place_raises_for_true_cross_process_reshard():
    from jax.sharding import NamedSharding

    mesh = sharding.build_mesh("data=8")

    class Stub:
        sharding = NamedSharding(mesh, P("data", None))
        committed = True
        ndim = 2
        shape = (8, 4)
        is_fully_addressable = False
        is_fully_replicated = False

    with pytest.raises(MXNetError, match="cannot re-place"):
        sharding.place(Stub(), mesh, P(None, "data"))


def test_param_bytes_accounting():
    mesh = sharding.build_mesh("data=4,model=2")
    placed = sharding.shard_params(
        {"w": mx.nd.zeros((8, 8)), "r": mx.nd.zeros((8, 8))},
        mesh, {"w": P("model", None)})
    per_dev, repl = sharding.param_bytes(placed.values())
    assert repl == 2 * 8 * 8 * 4
    assert per_dev == 8 * 8 * 4 // 2 + 8 * 8 * 4  # w halved, r replicated


# ----------------------------------------------------------------------
# executor_group._replicate no-op (pre-sharded set_params)
# ----------------------------------------------------------------------
def test_exec_group_replicate_noop_for_placed_array():
    from jax.sharding import NamedSharding

    from mxnet_tpu.module.executor_group import DataParallelExecutorGroup

    mesh = sharding.build_mesh("data=8")
    group = DataParallelExecutorGroup.__new__(DataParallelExecutorGroup)
    group._mesh = mesh
    group._repl_sharding = NamedSharding(mesh, P())
    group._multiprocess = False

    class Stub:  # cross-process-shaped array already replicated on the mesh
        sharding = NamedSharding(mesh, P())
        committed = True
        ndim = 1
        shape = (4,)
        is_fully_addressable = False
        is_fully_replicated = True

    stub = Stub()
    assert group._replicate(stub) is stub


# ----------------------------------------------------------------------
# sharded fused training
# ----------------------------------------------------------------------
def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


MLP_RULES = sharding.PartitionRules([
    (r"fc1_weight$", P("model", None)),
    (r"fc1_bias$", P("model")),
    (r"fc2_weight$", P(None, "model")),
], fallback=P(), name="mlp")


def _train(mod, batches, lr=0.1):
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": lr,
                                         "momentum": 0.9})
    for batch in batches:
        mod.forward_backward(batch)
        mod.update()
    args, auxs = mod.get_params()
    return ({k: v.asnumpy() for k, v in args.items()},
            {k: v.asnumpy() for k, v in auxs.items()})


def _batches(data_shape, label_shape, n, vocab=None):
    rng = np.random.RandomState(3)
    out = []
    for _ in range(n):
        if vocab:
            X = rng.randint(0, vocab, size=data_shape).astype(np.float32)
            y = rng.randint(0, vocab, size=label_shape).astype(np.float32)
        else:
            X = rng.randn(*data_shape).astype(np.float32)
            y = (rng.rand(*label_shape) * 8).astype(np.float32)
        out.append(mx.io.DataBatch(data=[mx.nd.array(X)],
                                   label=[mx.nd.array(y)]))
    return out


def _init_params(symbol, input_shapes):
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    rng = np.random.RandomState(11)
    args = {}
    inputs = set(input_shapes)
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in inputs:
            continue
        args[name] = mx.nd.array(
            (rng.randn(*shape) * 0.05).astype(np.float32)) \
            if shape else mx.nd.zeros(shape)
    auxs = {}
    for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes):
        auxs[name] = mx.nd.zeros(shape)
    return args, auxs


def test_mlp_sharded_fused_step_matches_single_device():
    # _init_params is deterministic; build a fresh dict per module (the
    # donated fused step consumes the buffers it is handed)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    batches = _batches((16, 64), (16,), 3)

    ref = mx.mod.Module(_mlp(), context=mx.cpu())
    ref.bind(data_shapes=[("data", (16, 64))],
             label_shapes=[("softmax_label", (16,))])
    ref.set_params(*_init_params(_mlp(), shapes))
    want_args, _ = _train(ref, batches)

    mesh = sharding.build_mesh("data=-1,model=2")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 64))],
             label_shapes=[("softmax_label", (16,))],
             mesh=mesh, partition_rules=MLP_RULES)
    mod.set_params(*_init_params(_mlp(), shapes))
    got_args, _ = _train(mod, batches)

    for name in want_args:
        np.testing.assert_allclose(got_args[name], want_args[name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    # the layout really shards: fc1_weight lives in (16, 64) halves
    w = mod._exec_group.execs[0].arg_dict["fc1_weight"]._data
    assert {tuple(s.data.shape) for s in w.addressable_shards} == {(16, 64)}


def _tiny_lm():
    from mxnet_tpu.models.transformer import get_transformer_lm

    return get_transformer_lm(vocab_size=64, num_layers=1, num_heads=2,
                              hidden=32, seq_len=16, block_q=16, block_k=16)


def test_transformer_megatron_2d_mesh_matches_single_device():
    """Acceptance: 2-D ("data","model") megatron-ruled transformer LM step
    == single-device baseline (fp32), with per-device param bytes
    measurably below replicated (asserted via the telemetry gauges)."""
    import mxnet_tpu.telemetry as telemetry

    net = _tiny_lm()
    shapes = {"data": (8, 16), "softmax_label": (8, 16)}
    batches = _batches((8, 16), (8, 16), 2, vocab=64)

    ref = mx.mod.Module(net, context=mx.cpu())
    ref.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8, 16))])
    ref.set_params(*_init_params(net, shapes))
    want_args, _ = _train(ref, batches, lr=0.05)

    telemetry._reset_for_tests()
    telemetry.enable()
    try:
        mesh = sharding.build_mesh("data=-1,model=2")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=[("data", (8, 16))],
                 label_shapes=[("softmax_label", (8, 16))],
                 mesh=mesh, partition_rules="transformer_megatron")
        mod.set_params(*_init_params(net, shapes))
        got_args, _ = _train(mod, batches, lr=0.05)

        snap = telemetry.registry().snapshot()
        sharded = snap.get("mxtpu_params_sharded_bytes")
        repl = snap.get("mxtpu_params_replicated_bytes")
        assert sharded and repl and sharded < repl
        assert telemetry.summary()["step"]["mesh"] == {"data": 4, "model": 2}
    finally:
        telemetry._reset_for_tests()

    for name in want_args:
        np.testing.assert_allclose(got_args[name], want_args[name],
                                   rtol=5e-4, atol=5e-5, err_msg=name)
    # tensor parallelism is real: the qkv weight is split across 'model'
    w = mod._exec_group.execs[0].arg_dict["layer0_qkv_weight"]._data
    assert {tuple(s.data.shape) for s in w.addressable_shards} == {(48, 32)}


def test_default_path_unchanged_without_rules():
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 64))],
             label_shapes=[("softmax_label", (8,))])
    assert mod._exec_group._rules is None
    assert mod._exec_group._mesh is None  # single ctx, no env knobs


def test_env_var_activation(monkeypatch):
    monkeypatch.setenv("MXNET_SHARDING_MESH", "data=-1,model=2")
    monkeypatch.setenv("MXNET_SHARDING_RULES", "replicated")
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 64))],
             label_shapes=[("softmax_label", (8,))])
    group = mod._exec_group
    assert group._rules is not None and group._rules.name == "replicated"
    assert sharding.mesh_axes(group._mesh) == {"data": 4, "model": 2}


def test_bind_rejects_uneven_rule_split():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=7, name="odd")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rules = sharding.PartitionRules([(r"odd_weight$", P("model", None))],
                                    fallback=P())
    mod = mx.mod.Module(net, context=mx.cpu())
    with pytest.raises(MXNetError, match="odd_weight"):
        mod.bind(data_shapes=[("data", (8, 64))],
                 label_shapes=[("softmax_label", (8,))],
                 mesh="data=-1,model=2",
                 partition_rules=rules)


# ----------------------------------------------------------------------
# what a step over a mesh is compiled with
# ----------------------------------------------------------------------
class _StubMesh:
    """All ``collective_compiler_options`` reads of a mesh: its devices'
    platforms and processes, and its shape."""

    def __init__(self, platforms, shape=None, processes=None):
        processes = processes or [0] * len(platforms)
        self.devices = np.array(
            [type("Device", (), {"platform": p, "process_index": i})()
             for p, i in zip(platforms, processes)],
            dtype=object).reshape(shape or len(platforms))


@pytest.mark.parametrize("mesh,engaged", [
    ("none", False), ("one_cpu", False), ("cpu", False), ("cpu_2d", False),
    ("one_tpu", False), ("mixed", False), ("tpu_2x2", False),
    ("tpu_two_hosts", False), ("tpu", True)])
def test_collective_compiler_options_follow_the_mesh(mesh, engaged):
    """``{}`` for no mesh, one device and host devices (an ``xla_tpu_*``
    option is an error to the CPU compiler), and for what was not measured
    (a mesh of two axes, a mesh over two processes); the
    asynchronous-collective set where several TPUs of this process make one
    data axis.  Nothing but the mesh decides."""
    import jax

    meshes = {
        "none": lambda: None,
        "one_cpu": lambda: sharding.build_mesh(
            "data=1", devices=jax.devices()[:1]),
        "cpu": lambda: sharding.build_mesh("data=-1"),
        "cpu_2d": lambda: sharding.build_mesh("data=-1,model=2"),
        "one_tpu": lambda: _StubMesh(["tpu"]),
        "mixed": lambda: _StubMesh(["tpu", "cpu"]),
        "tpu_2x2": lambda: _StubMesh(["tpu"] * 4, (2, 2)),
        "tpu_two_hosts": lambda: _StubMesh(["tpu"] * 4,
                                           processes=[0, 0, 1, 1]),
        "tpu": lambda: _StubMesh(["tpu"] * 4),
    }
    options = sharding.collective_compiler_options(meshes[mesh]())
    if not engaged:
        assert options == {}
        return
    assert options["xla_enable_async_all_reduce"] is True
    assert options[
        "xla_tpu_enable_async_collective_fusion_fuse_all_reduce"] is True
    # a copy: a caller's edit does not reach the next program
    options.clear()
    assert sharding.collective_compiler_options(meshes[mesh]())


def _fused_program(mod):
    ex = mod._exec_group.execs[0]
    (program,) = [v for k, v in ex._jit_cache.items() if k[0] == "fused"]
    return program


_CPU_OPTION = {"xla_cpu_enable_fast_min_max": False}


@pytest.mark.parametrize("path,options", [
    ("contexts", {}), ("contexts", _CPU_OPTION), ("rules", _CPU_OPTION)])
def test_compiler_options_join_the_stable_key(path, options, monkeypatch,
                                              tmp_path):
    """The options are part of the program: they reach ``jax.jit`` and the
    persistent key of the framework's compile cache; a step compiled with
    none keeps the key it had (an option the CPU compiler knows stands in
    for the TPU's).  A step that partition rules shard is never given any:
    only the data-parallel step was measured with them."""
    import jax

    from mxnet_tpu import compile_cache

    monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setattr(sharding, "collective_compiler_options",
                        lambda mesh: dict(options))
    seen = []
    real_jit = jax.jit

    def spy(fn, **kw):
        seen.append(kw.get("compiler_options"))
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    if path == "contexts":
        mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(4)])
        how = {}
    else:
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        how = {"mesh": sharding.build_mesh("data=-1,model=2"),
               "partition_rules": MLP_RULES}
    mod.bind(data_shapes=[("data", (16, 64))],
             label_shapes=[("softmax_label", (16,))], **how)
    shapes = {"data": (16, 64), "softmax_label": (16,)}
    mod.set_params(*_init_params(_mlp(), shapes))
    _train(mod, _batches((16, 64), (16,), 1))
    program = _fused_program(mod)
    assert isinstance(program, compile_cache.CachedFunction)
    keyed = [part for part in program._static_key
             if isinstance(part, tuple) and part[:1] == ("compiler_options",)]
    if options and path == "contexts":
        assert options in seen
        assert keyed == [("compiler_options", tuple(sorted(options.items())))]
    else:
        assert set(seen) == {None} and not keyed
    assert program._static_key[-1 - len(keyed)] == ("remat", 0)


@pytest.mark.parametrize("cached", [False, True])
def test_data_parallel_contexts_note_their_collectives(cached, monkeypatch,
                                                       tmp_path):
    """A module bound over several contexts counts, at its fused step's
    first build, the collectives of the compiled step and how many run
    asynchronously: on host devices the gradient all-reduce, and none.  The
    step monitor sets the pair beside the cost analysis: from the one
    re-compile that serves both or, where the compile cache built or loaded
    the step, from its entry's metadata with no compile of its own."""
    import mxnet_tpu.telemetry as telemetry
    from mxnet_tpu.telemetry import step_monitor

    if cached:
        monkeypatch.setenv("MXNET_COMPILE_CACHE_DIR", str(tmp_path / "cc"))
    analyses = []
    real = step_monitor.lower_and_analyze
    monkeypatch.setattr(step_monitor, "lower_and_analyze",
                        lambda *a: analyses.append(a) or real(*a))
    snaps = []
    for _ in range(2):  # the second module's step is a hit where cached
        telemetry._reset_for_tests()
        telemetry.enable()
        try:
            mod = mx.mod.Module(_mlp(),
                                context=[mx.cpu(i) for i in range(4)])
            mod.bind(data_shapes=[("data", (16, 64))],
                     label_shapes=[("softmax_label", (16,))])
            shapes = {"data": (16, 64), "softmax_label": (16,)}
            mod.set_params(*_init_params(_mlp(), shapes))
            _train(mod, _batches((16, 64), (16,), 2))
            snaps.append(telemetry.registry().snapshot())
        finally:
            telemetry._reset_for_tests()
    assert len(analyses) == (0 if cached else 2)
    for snap in snaps:
        assert snap["mxtpu_fused_step_collectives"] >= 1
        assert snap["mxtpu_fused_step_collectives_async"] == 0


@pytest.mark.parametrize("case", ["chain", "pair", "synchronous", "none"])
def test_collective_counts_reads_a_compiled_text(case):
    """One collective per channel: the steps of an asynchronous chain are
    one all-reduce, a start/done pair is one, and one the scheduler turned
    synchronous again (it keeps ``async_collective_name``) is not
    asynchronous."""
    from mxnet_tpu.hlo_analysis import collective_counts

    ar = ("  %%all-reduce.%d = bf16[8,8]{1,0} all-reduce(%%p), channel_id=%d, "
          "replica_groups=[1,4]<=[4], to_apply=%%add%s\n")
    texts = {
        "chain": (
            "HloModule m\n\n%async_collective_fusion.1 (p: bf16[8,8]) -> "
            "bf16[8,8] {\n" + ar % (1, 7, ', frontend_attributes={chain_id="0"}')
            + "}\n\n%async_collective_fusion.2 (p: bf16[8,8]) -> bf16[8,8] {\n"
            + ar % (2, 7, ', frontend_attributes={chain_id="0"}')
            + "}\n\nENTRY %main (p: bf16[8,8]) -> bf16[8,8] {\n"
            + ar % (3, 9, "") + "}\n", (2, 1)),
        "pair": (
            "ENTRY %main (p: f32[8]) -> f32[8] {\n"
            "  %ars = f32[8]{0} all-reduce-start(%p), channel_id=3, "
            "to_apply=%add\n"
            "  ROOT %ard = f32[8]{0} all-reduce-done(%ars)\n}\n", (1, 1)),
        "synchronous": (
            "ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {\n"
            + ar % (1, 4, ', frontend_attributes={async_collective_name='
                          '"all-reduce-start.5"}')
            + "  %t = (f32[2]{0}, f32[4]{0}) all-reduce(%a, %b), "
              "channel_id=5, to_apply=%add\n}\n", (2, 0)),
        "none": ("ENTRY %main (p: f32[8]) -> f32[8] {\n"
                 "  ROOT %n = f32[8]{0} negate(%p)\n}\n", (0, 0)),
    }
    text, (collectives, asynchronous) = texts[case]
    assert collective_counts(text) == {"collectives": collectives,
                                       "asynchronous": asynchronous}
