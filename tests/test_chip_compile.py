"""The main path's Pallas kernels, compiled by the TPU's own compiler for a
DESCRIBED v5e (no chip attached): flash forward, dQ and dK/dV at the grids
``chip_smoke.py`` runs.  What Mosaic refuses here (unaligned slices, too
much VMEM) it would refuse on the chip — interpret mode cannot see it.

The topology is described inside the module-scoped fixture only (never at
import): one process at a time may hold the TPU library, and under xdist
every worker imports every test file.  Compiles run in this process, with
JAX's persistent cache off (a described-device entry cannot be read back).
"""
import numpy as np
import pytest

# (batch, seq, heads, head_dim, block_q, block_k) — bf16, causal
LM_TRAIN = (4, 4096, 16, 128, 512, 512)       # Module step, default blocks
LM_TRAIN_BK1024 = (4, 4096, 16, 128, 512, 1024)  # the flash bench's grid
LM_SCORE = (2, 1024, 16, 128, 512, 512)       # serving: /predict scoring
# the benchmark's LM cells (perfbench: cgpt13b-train-s2048, -dp4, a chip's
# share): the backward kernels' grid, and the forward's own default block
LM_CELL = (4, 2048, 16, 128, 512, 512)
LM_CELL_FWD = (4, 2048, 16, 128, 2048, 2048)
GRIDS = {"lm_train": LM_TRAIN, "lm_train_bk1024": LM_TRAIN_BK1024,
         "lm_score": LM_SCORE, "lm_cell": LM_CELL,
         "lm_cell_fwd": LM_CELL_FWD}


@pytest.fixture(scope="module")
def topo():
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _qkv(grid, sharding):
    import jax
    import jax.numpy as jnp

    b, s, h, d, _, _ = grid
    return [jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16,
                                 sharding=sharding)] * 3


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(GRIDS))
def test_flash_forward_compiles_for_v5e(one_chip, name):
    import jax

    from mxnet_tpu.ops import attention as att

    grid = GRIDS[name]
    d, bq, bk = grid[3], grid[4], grid[5]

    def fwd(q, k, v):
        return att._flash_forward(q, k, v, True, 1.0 / np.sqrt(d), bq, bk,
                                  interpret=False)

    compiled = jax.jit(fwd).lower(*_qkv(grid, one_chip)).compile()
    _assert_mosaic(compiled)
    names = _custom_call_names(compiled.as_text())
    assert len(names) == 1 and names[0].startswith("flash_fwd"), names


def _custom_call_names(text):
    """The compiler names each Mosaic custom call after its kernel: what a
    device trace shows, and what the benchmark's per-kernel metrics read
    (``flash_fwd.3`` under a Symbol node's scope, ``jvp_flash_fwd_.1``
    under a transformation)."""
    import re

    return re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from _pallas_calls(getattr(sub, "jaxpr", sub))


def _blocks_fetched(eqn, operand):
    """{grid point: block index} of one operand of a ``pallas_call``."""
    import itertools

    import jax

    gm = eqn.params["grid_mapping"]
    imap = gm.block_mappings[operand].index_map_jaxpr
    return {pt: tuple(int(x) for x in jax.core.eval_jaxpr(
        imap.jaxpr, imap.consts, *pt))
        for pt in itertools.product(*(range(n) for n in gm.grid))}


def test_flash_fetches_no_dead_block_at_the_lm_cell():
    """Under the causal mask the pipeline copies a block from HBM when its
    index differs from the step's before: at the LM cell's grids every
    kernel's streamed operands change block on live steps only (the
    clamp), 10 of 16 steps a (batch, head) pair, and the forward's own
    default block is one step a pair."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    b, s, h, d, bq, bk = LM_CELL
    x = jax.ShapeDtypeStruct((1, s, 1, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, s), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def fwd(q, k, v):
        return att._flash_forward(q, k, v, True, scale, bq, bk, True)

    def bwd(q, k, v, o, lse, do):
        return att._flash_backward(q, k, v, o, lse, do, True, scale, bq, bk,
                                   True)

    calls = {e.params["name"]: e for e in
             list(_pallas_calls(jax.make_jaxpr(fwd)(x, x, x).jaxpr))
             + list(_pallas_calls(jax.make_jaxpr(bwd)(x, x, x, x, lse,
                                                      x).jaxpr))}
    # operand 1 is k in the q-major kernels; operand 0 is q in dK/dV
    for name, operand in (("flash_fwd", 1), ("flash_bwd_dq", 1),
                          ("flash_bwd_dkv", 0)):
        at = _blocks_fetched(calls[name], operand)
        n = s // bq
        fetched = 0
        for tile in range(n):
            row = [at[(0, tile, step)] for step in range(n)]
            fetched += len(set(row))
            live = (lambda j: j <= tile) if operand else \
                (lambda i: i >= tile)
            assert sorted(set(row)) == sorted(
                (0, j, 0) for j in range(n) if live(j)), (name, tile, row)
        assert fetched == 10, (name, fetched)

    (fq, fk), (dq, dk) = att._resolve(None, None, s, s, d, jnp.bfloat16,
                                      True)
    assert (fq, fk, dq, dk) == LM_CELL_FWD[4:] + LM_CELL[4:]
    assert att._resolve(512, 512, s, s, d, jnp.bfloat16, True) == \
        ((512, 512), (512, 512))


@pytest.mark.parametrize("name", ["lm_train", "lm_train_bk1024"])
def test_flash_backward_dq_dkv_compile_for_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention as att

    grid = GRIDS[name]
    b, s, h, d, bq, bk = grid
    q = _qkv(grid, one_chip)[0]
    lse = jax.ShapeDtypeStruct((b * h, s), jnp.float32, sharding=one_chip)

    def bwd(q, k, v, o, lse, do):
        return att._flash_backward(q, k, v, o, lse, do, True,
                                   1.0 / np.sqrt(d), bq, bk, interpret=False)

    compiled = jax.jit(bwd).lower(q, q, q, q, lse, q).compile()
    # two kernels: dQ, and dK/dV
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_flash_attention_vjp_through_the_public_call(one_chip):
    """The user-facing ``flash_attention`` (custom_vjp, blocks resolved the
    way the Module's op resolves them) differentiates into compiled
    kernels when told its operands are on a chip."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(LM_TRAIN, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    import re

    names = _custom_call_names(text)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(re.search(r"(?<![A-Za-z0-9])%s(?![A-Za-z0-9])" % kernel, n)
                   for n in names), (kernel, names)


# the decode cell's shapes (perfbench: cgpt13b-decode-closed)
DECODE = dict(lanes=8, heads=16, head_dim=128, page_size=16, num_pages=176,
              max_pages=128)


def test_paged_decode_kernel_compiles_for_v5e(one_chip):
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged

    d = DECODE
    f32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)
    row = f32(d["lanes"], d["heads"], d["head_dim"])
    plane = f32(d["num_pages"], d["page_size"], d["heads"], d["head_dim"])

    def step(q, k_new, v_new, k_pool, v_pool, table, at):
        return paged._kernel_decode(q, k_new, v_new, k_pool, v_pool, table,
                                    at, 1.0 / np.sqrt(d["head_dim"]))

    text = jax.jit(step, donate_argnums=(3, 4)).lower(
        row, row, row, plane, plane, i32(d["lanes"], d["max_pages"]),
        i32(d["lanes"])).compile().as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert len(names) == 1 and names[0].startswith("paged_decode"), names
    # donated, the planes go through the call in place: no copy of one
    assert not [line for line in text.splitlines()
                if "f32[176,16,16,128]" in line
                and re.search(r" copy(-start)?\(", line)]


@pytest.mark.parametrize("donated", [True, False])
def test_lane_program_moves_no_plane(one_chip, monkeypatch, donated):
    """The whole decode step of a 12-layer model at the cell's widths, as
    the engine's Executor builds it (planes carried), compiled for the
    chip: 12 kernels, the picked ids as a ``(lanes,)`` output beside the
    logits and, donated, no copy of a plane, staged or plain.
    Twelve layers because ``layer10`` sorts before ``layer1``: carried
    arguments handed over in a dict's order are paired crosswise with the
    outputs and XLA copies every plane.  Undonated (the rule under the
    framework's compile cache) each plane is copied once and the program
    still compiles: with the kernel's planes pinned to HBM this small pool
    aborted the compiler's memory-space assignment."""
    import re

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.models.transformer import (get_transformer_lm_decode,
                                              lane_plane_names)
    from mxnet_tpu.ops.interpret import bind

    # (earlier tests of this worker may have left a bundle attached)
    monkeypatch.setattr(compile_cache, "active", lambda: not donated)
    d = DECODE
    layers, vocab, hidden = 12, 512, d["heads"] * d["head_dim"]
    num_pages, max_pages = 24, 8
    symbol = get_transformer_lm_decode(
        vocab, layers, d["heads"], hidden, max_seq_len=128,
        page_size=d["page_size"])
    shapes = {"data": (d["lanes"],), "positions": (d["lanes"],),
              "source": (d["lanes"],), "prev_ids": (d["lanes"],),
              "page_table": (d["lanes"], max_pages)}
    planes = lane_plane_names(layers)
    shapes.update({name: (num_pages, d["page_size"], d["heads"],
                          d["head_dim"]) for name in planes})
    ex = symbol.simple_bind(mx.cpu(), grad_req="null", **shapes)
    ex.set_carried({name: 1 + i for i, name in enumerate(planes)})
    ex._bound = lambda fn: bind(fn, "tpu")  # as on a tpu context
    carried, args, aux, rng = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        ex._forward_args(None))
    assert ex._carried_names() == planes
    fwd = ex._get_fwd(False)
    assert ex.carry_donated == donated
    compiled = getattr(fwd, "_fn", fwd).lower(carried, args, aux, rng) \
        .compile()
    # the logits, the planes, then the ids the program picked itself: what
    # the engine reads of a step (generation/engine.py, ``_read_step``)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, str(o.dtype)) for o in (outs[0], outs[-1])] == \
        [((d["lanes"], vocab), "float32"), ((d["lanes"],), "float32")]
    assert len(outs) == 2 + len(planes)
    text = compiled.as_text()
    assert len(re.findall(r"= [^\n]*\"tpu_custom_call\"", text)) == layers
    plane = "f32[%d,16,16,128]" % num_pages
    copied = [line for line in text.splitlines() if plane in line
              and re.search(r" copy(-start)?\(", line)]
    assert not copied if donated else len(copied) >= 2 * layers


def _no_plane_is_copied(text, model, pages):
    """No line of the compiled module copies (plain or staged) an array of a
    K/V plane's shape: donated, the planes go through in place."""
    import re

    for shape in {"bf16[%d,16,%d]" % ((pages,) + shape)
                  for name, kind, shape, _ in model.planes()
                  if name.endswith(("_k_pool", "_v_pool"))}:
        copied = [line for line in text.splitlines() if shape in line
                  and re.search(r" copy(-start)?\(", line)]
        assert not copied, copied[:2]


# the hybrid family's lane program: reduced widths with the cell's head,
# state and convolution sizes twice over, and the cell's own widths
# (perfbench: g4hmicro-decode-closed16) over one period of its pattern
HYBRID = {
    "reduced": dict(hidden=512, periods=2, num_heads=8, kv_heads=2,
                    intermediate=1024, ssm_heads=16),
    "cell": dict(hidden=2048, periods=1, num_heads=32, kv_heads=8,
                 intermediate=8192, ssm_heads=64),
}


@pytest.mark.parametrize("widths", sorted(HYBRID))
def test_hybrid_lane_program_steps_its_state_in_one_pass(one_chip,
                                                         monkeypatch, widths):
    """The hybrid family's decode step (models/hybrid_lm.py: ``m m m m m
    a`` once or twice) as the engine's Executor builds it, planes carried
    and donated, compiled for the chip.  Each state-space layer's recurrent
    state goes through ONE Pallas call (``ssm_step``) that takes the plane
    where it lies and returns it in place: every plane is aliased in and
    out of the module, and no line copies, stages (``copy-start`` into
    memory space ``S(1)``: XLA's own pass over the whole plane had 4 of the
    cell case's 5 planes staged so), gathers, scatters or slices a state
    plane; no loop over lanes either.
    Each grouped-query attention layer's pages (bfloat16, a token one row of
    ``kv_heads x 64``) go through ONE ``paged_decode`` call that reads them
    where they lie: no copy of a K/V plane, staged or plain."""
    import re

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.models import HybridLM
    from mxnet_tpu.ops.interpret import bind

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    w = HYBRID[widths]
    lanes, slots, pages, max_pages, vocab = 16, 17, 24, 8, 512
    mamba = 5 * w["periods"]
    model = HybridLM(
        vocab_size=vocab, hidden=w["hidden"],
        layer_types=(["mamba"] * 5 + ["attention"]) * w["periods"],
        num_heads=w["num_heads"], kv_heads=w["kv_heads"], head_dim=64,
        intermediate=w["intermediate"], ssm_heads=w["ssm_heads"],
        ssm_head_dim=64, ssm_state=128, conv_kernel=4,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 64.0, logits_scaling=8.0)
    symbol = model.decode_symbol(128, 16)
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids", "state_slot")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        shapes[name] = ((pages, 16) if kind == "paged" else (slots,)) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    for name in symbol.list_arguments():
        if name not in shapes:
            types[name] = jnp.bfloat16  # the weights
    ex = symbol.simple_bind(mx.cpu(), grad_req="null", type_dict=types,
                            **shapes)
    assert str(ex.arg_dict["layer0_in_proj_weight"].dtype) == "bfloat16"
    ex.set_carried({name: 1 + i for i, name in enumerate(planes)})
    ex._bound = lambda fn: bind(fn, "tpu")  # as on a tpu context
    carried, args, aux, rng = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        ex._forward_args(None))
    assert ex._carried_names() == planes
    fwd = ex._get_fwd(False)
    assert ex.carry_donated
    compiled = getattr(fwd, "_fn", fwd).lower(carried, args, aux, rng) \
        .compile()
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, str(o.dtype)) for o in (outs[0], outs[-1])] == \
        [((lanes, vocab), "float32"), ((lanes,), "float32")]
    assert len(outs) == 2 + len(planes)
    text = compiled.as_text()
    assert " while(" not in text
    # one kernel a state-space layer, one an attention layer, and no other
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert sorted(n.split(".")[0] for n in names) == \
        ["paged_decode"] * w["periods"] + ["ssm_step"] * mamba, names
    _no_plane_is_copied(text, model, pages)
    # every plane goes through the module in place: argument i is output i
    # (the logits are output 0, the planes follow in the carried order)
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    aliased = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases))
    state_planes = [i for i, name in enumerate(planes)
                    if name.endswith("_ssm_state")]
    assert len(state_planes) == mamba
    assert all(aliased.get(str(1 + i)) == str(i) for i in state_planes), \
        aliases
    assert len(aliased) == len(planes)
    # the plane stays where it lies, in the layout it has
    state = "f32[%d,%d,64,128]" % (slots, w["ssm_heads"])
    lines = [line for line in text.splitlines() if state in line]
    assert lines
    moved = [line for line in lines if re.search(
        r" (copy|copy-start|copy-done|gather|scatter|dynamic-update-slice)"
        r"\(", line)]
    assert not moved, moved[:2]
    staged = [line for line in lines
              if re.search(re.escape(state) + r"\{[^}]*S\(1\)", line)]
    assert not staged, staged[:2]


@pytest.mark.parametrize("rows", [16, 512])
def test_routed_experts_cost_their_pairs_on_v5e(one_chip, rows):
    """One expert layer at the LFM2 cell's widths (32 experts of 1792 over
    hidden 2048, 4 a row) compiled for the chip, a lane bucket's 16 rows and
    a prefill bucket's 512: ONE Mosaic call, the repo's ``moe_grouped`` (both
    products and the gate; no ``ragged-dot``), whose counted FLOPs are the
    pairs' (rows x 4 x 6 x 2048 x 1792: NOT experts x rows x that, which a
    dense expansion would cost), and whose counted bytes hold each expert's
    weights once (0.70 GB: a lane step's 64 pairs hit at most 32 experts, a
    prefill's 2,048 sorted pairs in row tiles of 512 fetch an expert again
    only where its group crosses a tile's edge, 35 fetches for 32 experts at
    the most; around the call XLA counts the prefill's sort, gathers and the
    combine of 2,048 x 2,048 float32 at 0.6 GB more: under 2 x in all, where
    its own grouped kernel counted 2.5 x), each stacked leaf the call's
    operand whole and in bfloat16, with no copy, conversion, transpose or
    gather of it."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    H, F, E, K = 2048, 1792, 32, 4

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(g, router, bias, w13, w2):
        ids, w, _ = moe.route(g, router, bias, top_k=K)
        return moe.routed_experts(g, ids, w, w13, w2,
                                  grouped=moe._kernel_grouped)

    assert moe.experts_formulation("tpu", jnp.bfloat16, H, F) == "pallas"
    compiled = jax.jit(layer).lower(
        S((rows, H), jnp.bfloat16), S((E, H), jnp.bfloat16),
        S((E,), jnp.bfloat16), S((E, H, 2 * F), jnp.bfloat16),
        S((E, F, H), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert [n.split(".")[0] for n in names] == ["moe_grouped"], names
    _leaves_go_whole_into(text, "moe_grouped", calls=1)
    cost = compiled.cost_analysis()
    pairs = rows * K * 6 * H * F
    assert pairs <= cost["flops"] <= 1.5 * pairs
    weights = 3 * E * H * F * 2
    assert weights <= cost["bytes accessed"] <= \
        (1.3 if rows == 16 else 2.0) * weights
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows,k,held,experts,hidden,width", [
    (2048, 10, 36, 72, 4096, 768),     # g4hsmall-decode-closed16
    (2048, 8, 16, 256, 7680, 2048)],   # pangu718b-decode-closed16
    ids=["36_of_72", "16_of_256"])
def test_a_share_moves_its_own_pairs_rows_on_v5e(one_chip, rows, k, held,
                                                 experts, hidden, width):
    """A 2,048-token prefill's expert layer of the two long-prompt cells on
    the path ``experts_path`` picks for them (``"held"``), compiled for the
    chip: two Mosaic calls (``moe_grouped``, ``moe_combine``) and ONE loop,
    which gathers a row tile of the sorted pairs at a time into the layout
    the kernel reads, in a buffer that is allocated and not filled
    (``AllocateBuffer``: no pass over all the pairs' rows); nothing else in the
    module is as wide as all the pairs' rows (no gather, copy, pad, slice or
    transpose of ``pairs x hidden`` values in either dtype: the kernel's
    output goes whole into the combine), the leaves go whole into the
    kernel, and the scratch is the kernel's float32 output and twice the
    rows in the kernel's layout (8 bytes a pair's feature; moving every pair
    held 10.4 at 4,096 features)."""
    import math
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(g, ids, w, w13, w2):
        return moe.routed_experts(g, ids, w, w13, w2,
                                  grouped=moe._kernel_grouped,
                                  held=moe._kernel_held)

    pairs = rows * k
    assert moe.experts_path(
        moe.experts_formulation("tpu", jnp.bfloat16, hidden, width), held,
        experts, pairs) == "held"
    leaves = ("bf16[%d,%d,%d]" % (held, hidden, 2 * width),
              "bf16[%d,%d,%d]" % (held, width, hidden))
    compiled = jax.jit(layer).lower(
        S((rows, hidden), jnp.bfloat16), S((rows, k), jnp.int32),
        S((rows, k), jnp.float32), S((held, hidden, 2 * width), jnp.bfloat16),
        S((held, width, hidden), jnp.bfloat16)).compile()
    text = compiled.as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert [n.split(".")[0] for n in names] == ["moe_grouped",
                                                "moe_combine"], names
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r'custom_call_target="AllocateBuffer"', text)) == 1
    _leaves_go_whole_into(text, "moe_grouped", calls=1, leaves=leaves)
    # values as wide as every pair's row: the loop's buffer (carried, and
    # updated a tile at a time) and the kernel's output, nothing else
    made = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (?:bf16|f32)\[([\d,]+)\]"
                      r"\S* ([\w\-]+)\(")
    passes_on = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
                 "dynamic-update-slice"}
    for name, dims, opcode in (m.groups() for m in map(made.match,
                                                      text.splitlines()) if m):
        if math.prod(int(d) for d in dims.split(",")) < pairs * hidden:
            continue
        assert opcode in passes_on \
            or opcode == "fusion" and "dynamic-update-slice" in name \
            or opcode == "custom-call" and name.split(".")[0] in (
                "empty", "moe_grouped"), (name, dims, opcode)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        1.01 * pairs * hidden * 8


def _leaves_go_whole_into(text, kernel, calls,
                          leaves=("bf16[32,2048,3584]", "bf16[32,1792,2048]")):
    """Each stacked leaf of the cell's experts (the LFM2 cell's unless
    ``leaves`` says otherwise) is used by ``calls``
    instructions of the compiled module and no other, every one a call of
    ``kernel``: whole, in bfloat16, where it lies (no copy, slice, convert,
    transpose or gather of it, and no float32 twin)."""
    import re

    for leaf in leaves:
        uses = [line for line in text.splitlines()
                if leaf in line and re.match(r"\s*(ROOT )?%[\w.\-]+ = ", line)
                and " parameter(" not in line]
        assert len(uses) == calls and all(
            re.match(r"\s*(ROOT )?%" + kernel + r"[.\d]* = ", u)
            for u in uses), uses[:4]
        assert leaf.replace("bf16", "f32") not in text


def _compile_for_the_chip(symbol, shapes, types, planes, one_chip,
                          donated=True):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ops.interpret import bind

    for name in symbol.list_arguments():
        if name not in shapes:
            types[name] = jnp.bfloat16  # the weights
    ex = symbol.simple_bind(mx.cpu(), grad_req="null", type_dict=types,
                            **shapes)
    if planes:
        ex.set_carried({name: 1 + i for i, name in enumerate(planes)})
    ex._bound = lambda fn: bind(fn, "tpu")  # as on a tpu context
    call = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        ex._forward_args(None))
    fwd = ex._get_fwd(False)
    assert ex.carry_donated == donated or not planes
    return getattr(fwd, "_fn", fwd).lower(*call).compile()


def test_lfm2_lane_program_streams_each_expert_once(one_chip, monkeypatch):
    """The LFM2 family's decode step at the cell's widths, one period of its
    pattern (``c c a c``: one dense and three expert layers), as the
    engine's Executor builds it, planes carried and donated, compiled for
    the chip.  Each expert layer is two grouped products (XLA's own kernel,
    ``ragged-dot-none``) behind one ``ragged-dot-metadata``: every stacked
    leaf is the operand of exactly one product, whole and in bfloat16 (no
    copy, slice, convert or gather of it), so a step streams an expert's
    weights at most once.  The outputs end with ``next_ids`` and
    ``expert_load`` (3 layers x 32 experts, int32); every plane is aliased
    in and out.  The attention layer's pages (32 query heads over 8 K/V
    heads of 64, bfloat16) go through ONE ``paged_decode`` call, no K/V
    plane copied."""
    import re

    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.models import HybridLM
    from mxnet_tpu.ops.interpret import bind

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    lanes, slots, pages, max_pages, vocab = 16, 17, 24, 64, 512
    model = HybridLM(
        vocab_size=vocab, hidden=2048,
        layer_types=["conv", "conv", "attention", "conv"], num_heads=32,
        kv_heads=8, head_dim=64, intermediate=7168, conv_kernel=3,
        rotary_theta=1e6, qk_norm=True, num_experts=32, experts_per_token=4,
        expert_width=1792, num_dense_layers=1)
    symbol = model.decode_symbol(1024, 16)
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids", "state_slot")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        shapes[name] = ((pages, 16) if kind == "paged" else (slots,)) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    compiled = _compile_for_the_chip(symbol, shapes, types, planes, one_chip)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, str(o.dtype)) for o in (outs[0], outs[-2], outs[-1])] \
        == [((lanes, vocab), "float32"), ((lanes,), "float32"),
            ((3, 32), "int32")]
    assert len(outs) == 3 + len(planes) == 3 + 5
    text = compiled.as_text()
    assert " while(" not in text
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    kinds = sorted(n.split(".")[0] for n in names)
    assert kinds == ["moe_grouped"] * 3 + ["paged_decode"] \
        and "ragged-dot" not in text
    _no_plane_is_copied(text, model, pages)
    _leaves_go_whole_into(text, "moe_grouped", calls=3)
    scoped = re.findall(r"%moe_grouped[.\d]* = [^\n]*op_name=\"([^\"]*)\"",
                        text)
    assert len(scoped) == 3 and all(
        re.search(r"layer\d+_experts/moe_experts/", s) for s in scoped), scoped
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases)) == len(planes)


# the latent cell's pool (perfbench: pangu718b-decode-closed16): what XLA
# does with a plane depends on its size
LATENT_POOL = dict(lanes=16, pages=2433, max_pages=152)


def _pangu(layers):
    """openPangu-Ultra-MoE's block at its published widths, 16 of its 256
    experts held, a shared expert beside them, sandwich norms, an untied
    head: ``layers`` latent layers, the first of them dense."""
    from mxnet_tpu.models import HybridLM

    return HybridLM(
        vocab_size=512, hidden=7680, layer_types=["latent"] * layers,
        num_heads=128, kv_heads=128, head_dim=192, nope_dim=128, rope_dim=64,
        v_dim=128, q_rank=1536, kv_rank=512, intermediate=18432,
        rotary_theta=25.6e6, num_experts=256, experts_per_token=8,
        expert_width=2048, num_dense_layers=1, experts_held=16,
        routed_scaling=2.5, router_bias=False,
        shared_expert_width=2048, sandwich_norm=True, tied_head=False)


@pytest.mark.parametrize("donated", [True, False])
def test_latent_lane_program_carries_one_plane_a_layer(one_chip, monkeypatch,
                                                       donated):
    """The latent-attention family's decode step at openPangu-Ultra-MoE's
    widths, one dense and one expert layer, as the engine's Executor builds
    it, compiled for the chip at the cell's own pool.  ONE latent plane a
    layer, a token's row the 512 + 64 values in 640 columns (whole lane
    tiles: ``HybridLM.latent_row``), aliased in and out; the absorbed
    attention ONE ``paged_latent_decode`` call a layer under
    ``paged_attention/paged_attention_latent`` (both readers' scopes), which
    takes the plane where it lies: donated, no line of the module copies,
    gathers from or scatters into a plane, plain or staged into the fast
    memory ``S(1)``; undonated (the rule under the framework's compile
    cache) XLA copies each plane once and the program still compiles.  The
    routed products are the kernel ``moe_grouped`` at 7680 x 2048 (its tiles
    fit), the shared expert the dense MLP's three ops under
    ``layer<i>_shared_*``; no ``state_slot``."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: not donated)
    lanes, pages, max_pages = (LATENT_POOL[k] for k in ("lanes", "pages",
                                                        "max_pages"))
    model, vocab = _pangu(2), 512
    symbol = model.decode_symbol(max_pages * 16, 16)
    assert "state_slot" not in symbol.list_arguments()
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        assert (kind, shape) == ("paged", (640,))
        shapes[name] = (pages, 16) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    compiled = _compile_for_the_chip(symbol, shapes, types, planes, one_chip,
                                     donated)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, str(o.dtype)) for o in (outs[0], outs[-2], outs[-1])] \
        == [((lanes, vocab), "float32"), ((lanes,), "float32"),
            ((1, 256), "int32")]
    assert len(outs) == 3 + len(planes) == 3 + 2
    text = compiled.as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"[^\n]*"
                       r"op_name=\"([^\"]*)\"", text)
    assert sorted(n.split(".")[0] for n, _ in calls) == \
        ["moe_grouped", "paged_latent_decode", "paged_latent_decode"]
    assert all(re.search(r"layer\d_attn/paged_attention/"
                         r"paged_attention_latent/", scope)
               for n, scope in calls if n.startswith("paged_latent")), calls
    _leaves_go_whole_into(text, "moe_grouped", calls=1,
                          leaves=("bf16[16,7680,4096]", "bf16[16,2048,7680]"))
    ops = set(re.findall(r"op_name=\"([^\"]*)\"", text))
    assert any("layer1_experts/moe_experts/" in o for o in ops)
    for part in ("in", "gate", "out"):
        assert any(("layer1_shared_%s/" % part) in o for o in ops), part
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
    plane = r"bf16\[(%d,16|%d),640\]" % (pages, pages * 16)
    uses = [line for line in text.splitlines()
            if re.search(plane, line) and re.match(r"\s*(ROOT )?%[\w.\-]+ = ",
                                                    line)]
    moved = [line for line in uses if re.search(
        r" (copy|copy-start|copy-done|slice-start|slice-done|gather|scatter|"
        r"dynamic-update-slice|fusion)\(", line)]
    if not donated:
        assert moved and not any(" gather(" in line for line in moved)
        return
    assert not moved, moved[:2]
    assert not [line for line in uses
                if re.search(plane + r"\{[^}]*S\(1\)", line)]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases)) == len(planes)


def test_latent_decode_kernel_compiles_for_v5e(one_chip):
    """The kernel alone at the latent cell's shapes (16 lanes of 128 heads,
    rows of 512 + 64 in 640 columns, 2,433 pages, a table 152 wide): ONE
    custom call between XLA's ``q_c = q_n W_k`` and ``o_c W_v``, inside the
    default limit of the fast memory, and, donated, no copy of the plane."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged

    lanes, pages, max_pages = (LATENT_POOL[k] for k in ("lanes", "pages",
                                                        "max_pages"))
    heads, nope, rope, rank, v = 128, 128, 64, 512, 128

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(*operands):
        return paged._kernel_latent_decode(*operands, scale=0.1,
                                           in_place=True)

    text = jax.jit(step, donate_argnums=(4,)).lower(
        arg((lanes, heads, nope)), arg((lanes, heads, rope)),
        arg((lanes, rank + rope)), arg((heads * (nope + v), rank)),
        arg((pages, 16, 640)), arg((lanes, max_pages), jnp.int32),
        arg((lanes,), jnp.int32)).compile().as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert len(names) == 1 and names[0].startswith("paged_latent_decode")
    assert not [line for line in text.splitlines()
                if "bf16[%d,16,640]" % pages in line
                and re.search(r" copy(-start)?\(", line)]


# the three cells whose attention layers page grouped bfloat16 K/V
# (perfbench: g4hmicro-, lfm2moe-, g4hsmall-decode-closed16): one period of
# each pattern at the cell's widths, and the cell's OWN pool (pages, table
# width): what XLA does with a plane depends on its size
PAGED_CELLS = {
    "g4hmicro": (705, 44, dict(
        hidden=2048, layer_types=["mamba"] * 5 + ["attention"], num_heads=32,
        kv_heads=8, head_dim=64, intermediate=8192, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, conv_kernel=4,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 64.0, logits_scaling=8.0)),
    "lfm2moe": (705, 44, dict(
        hidden=2048, layer_types=["conv", "conv", "attention", "conv"],
        num_heads=32, kv_heads=8, head_dim=64, intermediate=7168,
        conv_kernel=3, rotary_theta=1e6, qk_norm=True, num_experts=32,
        experts_per_token=4, expert_width=1792, num_dense_layers=1)),
    "g4hsmall": (2433, 152, None),  # _granite_small
}


@pytest.mark.parametrize("cell,donated", [(c, True) for c in
                                          sorted(PAGED_CELLS)]
                         + [("g4hmicro", False)])
def test_hybrid_lane_program_reads_its_pages_where_they_lie(one_chip,
                                                            monkeypatch,
                                                            cell, donated):
    """The attention layer of each hybrid cell's lane program, planes carried
    and donated, compiled for the chip at the cell's own pool: ONE
    ``paged_decode`` call under the layer's ``paged_attention`` scope, which
    takes both planes where they lie (a token one row of ``kv_heads x
    head_dim`` bfloat16 lanes, so a page is whole tiles) and returns them in
    place.  No line of the module copies a K/V plane, whole or in slices,
    plain or staged into the fast memory ``S(1)`` (left free, XLA staged the
    704-token cells' 11.5 MB planes there and back around the call, and with
    the gather it relaid every plane out, 0.65 ms a step: PERF.md, PR 44),
    and nothing gathers from one.  Undonated (the rule under the
    framework's compile cache) XLA copies each plane once and the program
    still compiles: a plane held to the HBM that XLA must copy first aborts
    its memory-space assignment, so the kernel holds only donated ones
    (``interpret.carried_in_place``)."""
    import re

    import jax.numpy as jnp

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: not donated)
    from mxnet_tpu.models import HybridLM

    pages, max_pages, sizes = PAGED_CELLS[cell]
    model = _granite_small(["mamba", "attention"]) if sizes is None else \
        HybridLM(vocab_size=512, **sizes)
    lanes, slots = 16, 17
    symbol = model.decode_symbol(max_pages * 16, 16)
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids", "state_slot")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        shapes[name] = ((pages, 16) if kind == "paged" else (slots,)) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    text = _compile_for_the_chip(symbol, shapes, types, planes, one_chip,
                                 donated).as_text()
    calls = re.findall(r"%(paged_decode[.\d]*) = [^\n]*"
                       r"\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"", text)
    assert len(calls) == 1 and re.search(
        r"layer\d+_attn/paged_attention/", calls[0][1]), calls
    width = model.kv_heads * model.head_dim
    plane = r"bf16\[(%d,16|%d),%d\]" % (pages, pages * 16, width)
    uses = [line for line in text.splitlines()
            if re.search(plane, line) and re.match(r"\s*(ROOT )?%[\w.\-]+ = ",
                                                    line)]
    moved = [line for line in uses if re.search(
        r" (copy|copy-start|copy-done|slice-start|slice-done|gather|scatter|"
        r"dynamic-update-slice|fusion)\(", line)]
    if not donated:
        assert moved and not any(" gather(" in line for line in moved)
        return
    assert not moved, moved[:2]
    staged = [line for line in uses
              if re.search(plane + r"\{[^}]*S\(1\)", line)]
    assert not staged, staged[:2]
    # both planes aliased in and out of the module
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases)) == len(planes)


@pytest.mark.parametrize("b,L,heads", [
    (1, 2048, 128), (4, 1024, 128),      # g4hsmall's longest bucket, a batch
    (1, 512, 64), (1, 64, 64),           # g4hmicro's longest, and one chunk
    (1, 2560, 128),                      # the scoring program's, padded
])
def test_scan_kernel_compiles_for_v5e(one_chip, b, L, heads):
    """``_contrib_SSMScan``'s kernel at the two Granite cells' shapes
    (bfloat16 rows of heads x 64 + 2 x 128, chunks of 256): Mosaic takes the
    blocks of ``xbc`` as they lie, the transposed ``x`` and a chunk of 64
    rows, within the default scoped VMEM."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import ssm

    sizes = dict(heads=heads, head_dim=64, state=128, chunk=256)

    def scan(xbc, dt, A_log, D, dt_bias, length):
        return ssm.ssm_scan(xbc, dt, A_log, D, dt_bias, length,
                            scan=ssm._kernel_scan, **sizes)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vec = arg((heads,), jnp.float32)
    compiled = jax.jit(scan).lower(
        arg((b, L, heads * 64 + 256), jnp.bfloat16),
        arg((b, L, heads), jnp.bfloat16), vec, vec, vec,
        arg((b,), jnp.int32)).compile()
    _assert_mosaic(compiled)
    assert [n.split(".")[0] for n in _custom_call_names(
        compiled.as_text())] == ["ssm_scan"]


def _granite_small(layer_types, vocab=512):
    """Granite 4.0-H Small's block at its published widths, 36 of its 72
    experts held (perfbench/configs/granite-4.0-h-small.json)."""
    from mxnet_tpu.models import HybridLM

    return HybridLM(
        vocab_size=vocab, hidden=4096, layer_types=layer_types, num_heads=32,
        kv_heads=8, head_dim=128, intermediate=1536, ssm_heads=128,
        ssm_head_dim=64, ssm_state=128, conv_kernel=4, chunk=256,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 128.0, logits_scaling=16.0, num_experts=72,
        experts_per_token=10, expert_width=768, experts_held=36,
        router_bias=False, router_score="softmax", shared_expert_width=1536)


def test_granite_moe_lane_program_steps_state_and_streams_experts(
        one_chip, monkeypatch):
    """The decode step of the hybrid family WITH experts at Granite 4.0-H
    Small's widths, one state-space and one attention layer, as the engine's
    Executor builds it, compiled for the chip: the recurrent state of 128
    heads through ONE ``ssm_step`` call, in place; each layer's routed
    products ONE ``moe_grouped`` call at 4096 x 768 over the 36 held leaves
    (160 pairs a step: its tiles fit), whole and in bfloat16; the router
    (the softmax over the picked logits) under ``moe_router``, the shared
    MLP the dense MLP's three ops; ``expert_load`` (2 layers x 72) the last
    output; every plane aliased in and out; the attention layer's pages (32
    query heads over 8 K/V heads of 128, bfloat16: the ``g4hsmall`` cell's)
    through ONE ``paged_decode`` call, no K/V plane copied."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    lanes, slots, pages, max_pages, vocab = 16, 17, 40, 152, 512
    model = _granite_small(["mamba", "attention"], vocab)
    symbol = model.decode_symbol(2432, 16)
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids", "state_slot")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        shapes[name] = ((pages, 16) if kind == "paged" else (slots,)) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    compiled = _compile_for_the_chip(symbol, shapes, types, planes, one_chip)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [(o.shape, str(o.dtype)) for o in (outs[0], outs[-2], outs[-1])] \
        == [((lanes, vocab), "float32"), ((lanes,), "float32"),
            ((2, 72), "int32")]
    assert len(outs) == 3 + len(planes) == 3 + 4
    text = compiled.as_text()
    assert " while(" not in text and "ragged-dot" not in text
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert sorted(n.split(".")[0] for n in names) == \
        ["moe_grouped", "moe_grouped", "paged_decode", "ssm_step"]
    _no_plane_is_copied(text, model, pages)
    _leaves_go_whole_into(text, "moe_grouped", calls=2,
                          leaves=("bf16[36,4096,1536]", "bf16[36,768,4096]"))
    ops = set(re.findall(r"op_name=\"([^\"]*)\"", text))
    for i in (0, 1):
        assert any(("layer%d_router/moe_router/" % i) in o for o in ops)
        assert any(("layer%d_experts/moe_experts/" % i) in o for o in ops)
        for part in ("in", "gate", "out"):
            assert any(("layer%d_shared_%s/" % (i, part)) in o for o in ops)
    state = "f32[%d,128,64,128]" % slots
    moved = [line for line in text.splitlines() if state in line
             and re.search(r" (copy|copy-start|copy-done|gather|scatter|"
                           r"dynamic-update-slice)\(", line)]
    assert not moved, moved[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases)) == len(planes)


def test_granite_moe_prefill_scans_128_heads_and_groups_its_pairs(
        one_chip, monkeypatch):
    """The same two layers' prefill of the cell's longest bucket (one prompt
    of 2,048: 8 chunks of 256 through the scan at 128 heads, 20,480 (row,
    pick) pairs a layer, about half of them on held experts), compiled for
    the chip: the routed products ``moe_grouped`` (no ``ragged-dot``) and,
    since the graph holds 36 of the router's 72 experts and a prefill's
    pairs fill 40 row tiles, the path that moves the held pairs' rows alone
    (``experts_path``: ``moe_combine`` after each ``moe_grouped``), the
    scan ONE ``ssm_scan`` call a state-space layer, under the scope
    ``ssm_scan``, the attention layer's four blocks of queries ONE
    ``flash_fwd`` call under its node (32 heads over 8:
    ``sequence_formulation``), the scratch memory under 2 GB (the whole ten
    layers' program takes 1.22 GB: my compile, PR 43), and the slabs the
    layers carry into decode beside the logits."""
    import re

    import jax

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    L, vocab = 2048, 512
    model = _granite_small(["mamba", "attention"], vocab)
    compiled = _compile_for_the_chip(
        model.prefill_symbol(L, 2432), {"data": (1, L), "length": (1,)}, {},
        [], one_chip)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.shape for o in outs] == [
        (1, L, vocab), (1, 128, 64, 128), (1, 3, 8448), (1, L, 8 * 128),
        (1, L, 8 * 128)]
    text = compiled.as_text()
    assert "ragged-dot" not in text
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert [n.split(".")[0] for n in names] == [
        "ssm_scan", "moe_grouped", "moe_combine", "flash_fwd", "moe_grouped",
        "moe_combine"]
    ops = set(re.findall(r"op_name=\"([^\"]*)\"", text))
    assert any("layer1_experts/moe_experts/" in o and "moe_combine" in o
               for o in ops)
    assert any("layer1_attn/" in scope for scope in re.findall(
        r"%flash_fwd[\w.\-]* = [^\n]*op_name=\"([^\"]*)\"", text))
    assert "f32[1,8,4,512," not in text   # a block's scores
    # the scan is the kernel, under the scope the per-layer metrics read;
    # the bucket's decay tensor (268 MB a layer before PR 50) is gone
    assert any("layer0_ssm/ssm_scan/" in o and o.endswith("pallas_call")
               for o in ops)
    assert "f32[1,8,256,256,128]" not in text
    assert any("layer1_router/moe_router/" in o for o in ops)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# the sliding-window cell's pool (perfbench: lagunaxs2-decode-closed16)
LAGUNA_POOL = dict(lanes=16, pages=5121, max_pages=320, slots=17)


def _laguna(vocab=512):
    """Laguna-XS.2's block at its published widths, ONE period of its
    pattern (a full layer of 48 query heads, YaRN on half a head, then three
    sliding layers of 64 over a window of 512, plain angles; 8 K/V heads of
    128 in all; a gate a head; layer 0 dense, then 32 of 256 experts of 512
    held beside a shared expert; an untied head)."""
    from mxnet_tpu.models import HybridLM

    return HybridLM(
        vocab_size=vocab, hidden=2048,
        layer_types=["attention", "window", "window", "window"],
        num_heads=48, window_heads=64, kv_heads=8, head_dim=128, window=512,
        rotary_theta=500000.0, rotary_dim=64,
        rotary_scaling=dict(factor=64.0, original_max=4096, beta_fast=64.0,
                            beta_slow=1.0,
                            attention_factor=1.4158883083359672),
        window_rotary_theta=10000.0, attn_gate=True, intermediate=8192,
        eps=1e-6, num_experts=256, experts_per_token=8, expert_width=512,
        num_dense_layers=1, experts_held=32, routed_scaling=2.5,
        router_bias=False, shared_expert_width=512, tied_head=False)


def test_laguna_lane_program_holds_rings_beside_pages(one_chip, monkeypatch):
    """The sliding-window family's decode step at Laguna-XS.2's widths, one
    period, as the engine's Executor builds it, planes carried and donated,
    compiled for the chip at the cell's own pool (16 lanes, 320 pages a
    lane, 17 ring slots).  The full layer's attention is ONE ``paged_decode``
    call at 48 query heads over rows of 8 x 128 bfloat16 lanes, under
    ``paged_attention``; the three sliding layers run under
    ``window_attention`` over ring planes ``(17, 512, 1024)`` that are
    aliased in and out of the module beside the two paged planes: no ring
    plane is copied whole (the step gathers 16 slots and scatters 16 rows);
    the gates under their nodes' names; the routed products
    ``moe_grouped``."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    lanes, pages, max_pages, slots = (LAGUNA_POOL[k] for k in (
        "lanes", "pages", "max_pages", "slots"))
    model, vocab = _laguna(), 512
    symbol = model.decode_symbol(max_pages * 16, 16)
    shapes = {name: (lanes,) for name in ("data", "positions", "source",
                                          "prev_ids", "state_slot")}
    shapes["page_table"] = (lanes, max_pages)
    types, planes = {}, []
    for name, kind, shape, dtype in model.planes():
        shapes[name] = ((pages, 16) if kind == "paged" else (slots,)) + shape
        types[name] = jnp.dtype(dtype)
        planes.append(name)
    assert [shapes[n] for n in planes] == [(pages, 16, 1024)] * 2 \
        + [(slots, 512, 1024)] * 6
    compiled = _compile_for_the_chip(symbol, shapes, types, planes, one_chip)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(outs) == 3 + len(planes)
    assert outs[0].shape == (lanes, vocab) and outs[-1].shape == (3, 256)
    text = compiled.as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"[^\n]*"
                       r"op_name=\"([^\"]*)\"", text)
    assert sorted(n.split(".")[0] for n, _ in calls) == \
        ["moe_grouped"] * 3 + ["paged_decode"]
    assert all(re.search(r"layer0_attn/paged_attention/", scope)
               for n, scope in calls if n.startswith("paged_decode")), calls
    ops = set(re.findall(r"op_name=\"([^\"]*)\"", text))
    for i in (1, 2, 3):
        assert any(("layer%d_attn/window_attention/" % i) in o for o in ops)
    for i in range(4):
        assert any(("layer%d_attn_gate" % i) in o for o in ops), i
    # no ring plane is copied whole, and every plane is aliased in and out
    ring = r"bf16\[%d,512,1024\]" % slots
    moved = [line for line in text.splitlines()
             if re.search(ring, line.split(" = ")[-1].split("(")[0])
             and re.search(r" (copy|copy-start)\(", line)]
    assert not moved, moved[:2]
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert len(re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases)) == len(planes)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


def test_laguna_prefill_attends_in_blocks_and_returns_rings(one_chip,
                                                            monkeypatch):
    """The same period's prefill of the cell's longest bucket (one prompt of
    4,096) at the cell's slice of the vocabulary, compiled for the chip: the
    full layer's K and V slabs as the planes hold a token, each sliding
    layer's rings ``(1, 512, 1024)``, and the attention ONE ``flash_fwd``
    call a layer (the cell's 20 layers are five such periods: 20 calls),
    the full layer's under its node and by heads, the sliding layers' under
    ``window_attention`` and by rows: no block's float32 scores are left in the text (48
    x 512 x 4,096 and 64 x 512 x 1,023 of them a block before PR 52), and
    the scratch memory is under 1 GB."""
    import re

    import jax

    from mxnet_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "active", lambda: False)
    L, vocab = 4096, 12544
    compiled = _compile_for_the_chip(
        _laguna(vocab).prefill_symbol(L, 5120),
        {"data": (1, L), "length": (1,)}, {}, [], one_chip)
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert [o.shape for o in outs] == [(1, L, vocab)] \
        + [(1, L, 1024)] * 2 + [(1, 512, 1024)] * 6
    text = compiled.as_text()
    calls = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"[^\n]*"
                       r"op_name=\"([^\"]*)\"", text)
    flash = sorted(scope for n, scope in calls if n.startswith("flash_fwd"))
    assert len(flash) == 4 and "layer0_attn/" in flash[0] \
        and "window_attention" not in flash[0], flash
    for i in (1, 2, 3):
        assert ("layer%d_attn/window_attention/" % i) in flash[i], flash
    # the band's calls take and leave a token as one row of 64 x 128 lanes
    # (no transpose around them); the full layer's go by heads
    by_rows = [line for line in text.splitlines() if re.match(
        r"\s*%flash_fwd[\w.\-]* = \(bf16\[1,4096,8192\]", line)]
    assert len(by_rows) == 3 and all("window_attention" in x
                                     for x in by_rows), by_rows
    for scores in ("f32[1,8,6,512,", "f32[1,8,8,512,", "f32[1,8,6,4096,4096]",
                   "f32[1,8,8,4096,4096]"):
        assert scores not in text, scores
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("L,heads,window,grid", [
    (4096, 64, 512, (64, 8, 2)), (4096, 48, 0, (48, 2, 2)),
    (5120, 48, 0, (48, 5, 5))])
def test_sequence_kernel_compiles_for_v5e(one_chip, L, heads, window, grid):
    """The flash forward alone at the sliding-window cell's longest prefill:
    one prompt of 4,096, 64 query heads over 8 K/V heads under a band of 512
    (two 512 x 512 steps a q tile: the grid walks the band's blocks and no
    other) and 48 over 8 under the causal mask (the forward's default block
    of 2,048; 1,024 in the cell's 5,120-token scoring program, which 2,048
    does not divide); ``sequence_formulation`` says ``pallas`` for all."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged

    kv, hd = 8, 128
    assert paged.sequence_formulation("tpu", L, heads, kv, hd, jnp.bfloat16,
                                      False) == "pallas"

    def arg(n):
        return jax.ShapeDtypeStruct((1, L, n, hd), jnp.bfloat16,
                                    sharding=one_chip)

    def attend(q, k, v):
        return paged._kernel_sequence(q, k, v, scale=hd ** -0.5,
                                      window=window, interpret=False)

    args = arg(heads), arg(kv), arg(kv)
    (call,) = _pallas_calls(jax.make_jaxpr(attend)(*args).jaxpr)
    assert call.params["grid_mapping"].grid == grid
    compiled = jax.jit(attend).lower(*args).compile()
    names = _custom_call_names(compiled.as_text())
    assert len(names) == 1 and names[0].startswith("flash_fwd"), names
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_paged_decode_takes_48_heads_over_rows_of_8_by_128(one_chip):
    """The kernel alone at the sliding-window cell's full layers: 16 lanes
    of 48 query heads, six to a K/V head, over bfloat16 rows of 8 x 128
    lanes, 320 pages a lane (``decode_formulation`` says ``pallas`` for
    it)."""
    import re

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import paged

    lanes, pages, max_pages = (LAGUNA_POOL[k] for k in ("lanes", "pages",
                                                        "max_pages"))
    assert paged.decode_formulation("tpu", 48, 128, jnp.bfloat16, kv_heads=8,
                                    rows=True, page_size=16) == "pallas"

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k_new, v_new, k_pool, v_pool, table, at):
        return paged._kernel_decode(q, k_new, v_new, k_pool, v_pool, table,
                                    at, 128 ** -0.5)

    new, plane = arg((lanes, 8, 128)), arg((pages, 16, 1024))
    text = jax.jit(step, donate_argnums=(3, 4)).lower(
        arg((lanes, 48, 128)), new, new, plane, plane,
        arg((lanes, max_pages), jnp.int32), arg((lanes,), jnp.int32)
    ).compile().as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*\"tpu_custom_call\"", text)
    assert len(names) == 1 and names[0].startswith("paged_decode"), names
    assert not [line for line in text.splitlines()
                if "bf16[5121,16,1024]" in line
                and re.search(r" copy(-start)?\(", line)]


@pytest.mark.parametrize("chips", [4, 1])
def test_data_parallel_step_reduces_under_its_compute(topo, monkeypatch,
                                                       chips):
    """A ``Module``'s fused step (three 2048-wide layers, momentum SGD,
    float32) as the executor builds it, compiled for the described chips.
    Bound over four of them, the mesh's compile options engage and the
    gradient all-reduces run asynchronously, as chains of
    ``async_collective_fusion`` steps; bound to one chip no option is set
    and the text holds no collective.  (The arrays live on host devices,
    where a described device can hold none: the executor is handed the
    described mesh and the arguments' shapes carry its shardings.)"""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import mxnet_tpu as mx
    from mxnet_tpu.hlo_analysis import collective_counts
    from mxnet_tpu.ops.interpret import bind

    net = mx.sym.Variable("data")
    for i in range(3):
        net = mx.sym.FullyConnected(net, num_hidden=2048, name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(chips)])
    mod.bind(data_shapes=[("data", (32 * chips, 2048))],
             label_shapes=[("softmax_label", (32 * chips,))])
    mod.init_params()
    mod.init_optimizer(kvstore="local", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    ex = mod._exec_group.execs[0]
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    if chips > 1:
        ex._kernel_mesh = (mesh, "data")
        ex._bound = lambda fn: bind(fn, "tpu", mesh, "data")
    else:
        ex._bound = lambda fn: bind(fn, "tpu")
    seen = []
    real_jit = jax.jit

    def spy(fn, **kw):
        seen.append(kw.get("compiler_options"))
        return real_jit(fn, **kw)

    with monkeypatch.context() as patch:
        patch.setattr(jax, "jit", spy)
        program, args, *_ = ex._fused_pack(mod._optimizer, mod._updater,
                                           mod._exec_group.param_names)

    def described(x):
        spec = getattr(getattr(x, "sharding", None), "spec",
                       PartitionSpec())
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    text = program.lower(*jax.tree_util.tree_map(described, args)) \
        .compile().as_text()
    counts = collective_counts(text)
    if chips == 1:
        assert seen == [None]
        assert counts == {"collectives": 0, "asynchronous": 0}
        return
    assert seen == [mx.sharding.collective_compiler_options(mesh)] and seen[0]
    # three weights of 16.8 MB, each a reduction of its own; the biases'
    assert counts["collectives"] >= 3
    assert counts["asynchronous"] >= 1
    assert "async_collective_fusion" in text
