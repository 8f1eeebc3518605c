#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, at the full width of the dense transformer LM
(vocab 32000, hidden 2048, 16 heads x 128, 6 layers, flash attention, bf16
compute over f32 master weights), through the entry points a user calls:

  trainer  ``mx.models.get_transformer_lm`` -> ``mx.mod.Module(context=
           mx.tpu(0), compute_dtype="bfloat16")`` -> bind / init_params /
           init_optimizer("adam") -> forward_backward + update at seq 4096,
           batch 4, one seeded batch repeated;
  server   ``mx.serving.InferenceServer(ctx=mx.tpu(0), generator_spec=...)``
           + ``serve_http()`` + ``POST /generate`` (continuous batching over
           a KV pool of 8 lanes x 1024 tokens), and the prefill logits of
           one prompt against the same ``Predictor`` bound on ``mx.cpu()``.

``--chips 4`` runs ONLY the data-parallel trainer over four chips
(``context=[mx.tpu(i) for i in range(4)]``) against the one-chip run of the
same seeded batch.  ResNet-50 is left out on purpose (cold compile time).

One process, no children.  The device check comes first: without a TPU the
script fails within seconds instead of running the model on the host.  The
LAST line of standard output is the contracted JSON object, also on failure
(``"ok": false``, exit code 1); everything else is printed before it.
"""
import argparse
import functools
import gc
import json
import math
import os
import sys
import threading
import time
import traceback

LM = dict(vocab=32000, hidden=2048, heads=16, layers=6)
TRAIN = dict(LM, seq=4096, batch=4, steps=3, lr=3e-4)
SERVE = dict(LM, max_seq=1024, lanes=8, page_size=16,
             prompt_lens=(128, 256, 512), new_tokens=16)
# the decode cell's shapes (perfbench: cgpt13b-decode-closed), where the
# paged-attention kernel is compared with the XLA formulation
PAGED = dict(lanes=8, num_pages=176, page_size=16, heads=16, head_dim=128,
             max_pages=128, positions=(63, 351))
# the hybrid cells' attention layers (perfbench: g4hmicro- / lfm2moe- and
# g4hsmall-decode-closed16): 32 query heads over 8 K/V heads of 64 and of
# 128, bfloat16, a token held as ONE row of the plane
PAGED_GROUPED = (
    dict(lanes=16, num_pages=705, page_size=16, heads=32, kv_heads=8,
         head_dim=64, max_pages=44, positions=(176, 528), dtype="bfloat16"),
    dict(lanes=16, num_pages=2433, page_size=16, heads=32, kv_heads=8,
         head_dim=128, max_pages=152, positions=(608, 1824),
         dtype="bfloat16"))
# the latent cell's attention layers (perfbench: pangu718b-decode-closed16):
# 128 heads over ONE plane of rows [c | k_r], 512 + 64 values in 640 columns
PAGED_LATENT = dict(lanes=16, num_pages=2433, page_size=16, heads=128,
                    nope=128, rope=64, rank=512, v=128, row=640,
                    max_pages=152, positions=(608, 1824))
# the two Granite cells' state-space layers in prefill (perfbench: g4hsmall-
# and g4hmicro-decode-closed16): their longest bucket with prompts that end
# inside a chunk and on a chunk's edge, and the short mix's one-chunk bucket
SSM_SCAN = (dict(L=2048, heads=128, lengths=(1300, 2048)),
            dict(L=512, heads=64, lengths=(200, 512, 256, 1)),
            dict(L=64, heads=64, lengths=(40,)))
PAGED_TOL = 2e-2        # max|kernel - XLA| (XLA's products are one bf16 pass)
SCAN_Y_TOL = 1e-2       # max|kernel - XLA| / max|XLA| of y, rounded to bf16
SCAN_STATE_TOL = 1e-4   # the same of the final state, float32 in both
LOSS0_BOUND = 1.0       # |step-0 loss - ln(vocab)|
DP_LOSS_TOL = 0.05      # |dp4 loss - one-chip loss|, every step
LOGITS_REL_TOL = 0.05   # max|tpu - cpu| prefill logits / max|cpu logits|


def say(msg):
    print("[smoke] %s" % msg, flush=True)


def final_line(ok, devices):
    """The one contracted line: exactly {ok, device:{platform,kind,count}}."""
    first = devices[0] if devices else None
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": getattr(first, "platform", "none"),
                   "kind": getattr(first, "device_kind", "none"),
                   "count": len(devices)}})


def check(cond, what):
    if not cond:
        raise AssertionError("check failed: %s" % what)
    say("ok: %s" % what)


def _cache_entries():
    import jax

    d = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(d)) if d and os.path.isdir(d) else 0
    return d, n


def _devices_of(arrays):
    return sorted({d for a in arrays for d in a._data.devices()},
                  key=lambda d: d.id)


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def trainer_phase(cfg, contexts, seed=0):
    """A few fused Module train steps of the LM on ``contexts`` (one
    context: one device; several: one data-parallel program over their
    mesh).  Returns {"losses", "step_ms", "compile_s", "param_devices",
    "data_devices", "compiled_text"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx

    platform = contexts[0].jax_device().platform
    net = mx.models.get_transformer_lm(
        vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], hidden=cfg["hidden"], seq_len=cfg["seq"])
    rng = np.random.RandomState(seed)
    X = rng.randint(0, cfg["vocab"],
                    size=(cfg["batch"], cfg["seq"])).astype(np.float32)
    Y = (X + 1) % cfg["vocab"]
    it = mx.io.NDArrayIter(X, Y, batch_size=cfg["batch"],
                           label_name="softmax_label")
    mod = mx.mod.Module(net, label_names=("softmax_label",),
                        context=contexts, compute_dtype="bfloat16")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mx.random.seed(seed)
    np.random.seed(seed)
    mod.init_params(initializer=mx.init.Xavier(factor_type="in",
                                               magnitude=2.34))
    mod.init_optimizer(kvstore="local", optimizer="adam",
                       optimizer_params={"learning_rate": cfg["lr"]})
    batch = it.next()
    labels = Y.reshape(-1).astype(np.int32)

    @jax.jit
    def nll(probs, lab):
        # gather first: a float32 copy of the (b*s, vocab) output would be
        # 2 GB next to a step that already fills the chip
        p = jnp.take_along_axis(probs, lab[:, None], 1).astype(jnp.float32)
        return -jnp.mean(jnp.log(p))

    losses, step_s = [], []
    for _ in range(cfg["steps"]):
        t0 = time.perf_counter()
        mod.forward_backward(batch)
        mod.update()
        out = mod.get_outputs()[0]
        losses.append(float(nll(out._data, labels)))  # blocks on the step
        step_s.append(time.perf_counter() - t0)
    check(mod._fused_ok, "train step is the single fused program")

    ex = mod._exec_group.execs[0]
    params = [ex.arg_dict[n] for n in mod._exec_group.param_names]
    states = [s for st in mod._updater.states.values()
              for s in (st if isinstance(st, (tuple, list)) else [st])
              if s is not None]
    param_devs = _devices_of(params)
    want = [c.jax_device() for c in contexts]
    check(param_devs == want and _devices_of(states) == want
          and _devices_of([out]) == want,
          "parameters, optimizer state and outputs live on %s" % want)
    check(all(d.platform == platform for d in param_devs),
          "those devices are %s devices" % platform)
    data = ex.arg_dict["data"]._data
    shard_devs = sorted((s.device for s in data.addressable_shards),
                        key=lambda d: d.id)
    check(len(set(shard_devs)) == len(contexts)
          and data.addressable_shards[0].data.shape[0]
          == cfg["batch"] // len(contexts),
          "batch of %d split %d per device over %s"
          % (cfg["batch"], cfg["batch"] // len(contexts), shard_devs))

    fn, abstract = ex._fused_introspect
    t0 = time.perf_counter()
    text = fn.lower(*abstract).compile().as_text()
    say("compiled step text: %d bytes, re-lowered in %.1f s (cache hit "
        "expected)" % (len(text), time.perf_counter() - t0))
    if platform == "tpu":
        check("tpu_custom_call" in text,
              "flash kernels are in the step compiled (tpu_custom_call), "
              "not interpreted")
    if len(contexts) > 1:
        check("all-reduce" in text, "compiled step contains an all-reduce")
    check(all(math.isfinite(l) for l in losses), "losses finite: %s"
          % ["%.4f" % l for l in losses])
    check(abs(losses[0] - math.log(cfg["vocab"])) <= LOSS0_BOUND,
          "step-0 loss %.4f within %.1f of ln(%d)=%.4f"
          % (losses[0], LOSS0_BOUND, cfg["vocab"], math.log(cfg["vocab"])))
    check(losses[-1] < losses[0], "loss falls over the repeated batch")
    say("trainer on %d device(s): first step (compile+run) %.1f s, later "
        "steps %s ms" % (len(contexts), step_s[0],
                         ["%.1f" % (s * 1e3) for s in step_s[1:]]))
    return {"losses": losses, "step_ms": [s * 1e3 for s in step_s[1:]],
            "compile_s": step_s[0], "param_devices": param_devs,
            "data_devices": shard_devs, "compiled_text": text}


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def make_lm_params(cfg, ctx, seed=0):
    """Seeded random LM weights as NDArrays on ``ctx`` (LayerNorm gains 1,
    biases 0, everything else N(0, 0.02)) for ``get_transformer_lm`` at
    ``seq_len=cfg["max_seq"]``; returns (scoring symbol, params)."""
    import numpy as np

    import mxnet_tpu as mx

    net = mx.models.get_transformer_lm(
        vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], hidden=cfg["hidden"], seq_len=cfg["max_seq"])
    shapes, _, _ = net.infer_shape(data=(1, cfg["max_seq"]),
                                   softmax_label=(1, cfg["max_seq"]))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shp in zip(net.list_arguments(), shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("_gamma"):
            val = np.ones(shp, np.float32)
        elif name.endswith(("_beta", "_bias")):
            val = np.zeros(shp, np.float32)
        else:
            val = (rng.randn(*shp) * 0.02).astype(np.float32)
        params[name] = mx.nd.array(val, ctx)
    return net, params


def _post_generate(host, port, prompt, max_new):
    """One POST /generate; returns (tokens, arrival times, done record)."""
    import http.client

    conn = http.client.HTTPConnection(host, port, timeout=600)
    conn.request("POST", "/generate", json.dumps(
        {"prompt": prompt, "max_new_tokens": max_new}),
        {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        raise RuntimeError("POST /generate -> %d %s"
                           % (resp.status, resp.read()[:300]))
    tokens, stamps, done = [], [], None
    for raw in resp:
        rec = json.loads(raw)
        if "token" in rec:
            tokens.append(rec["token"])
            stamps.append(time.perf_counter())
        elif rec.get("done"):
            done = rec
        else:
            raise RuntimeError("stream failed in-band: %r" % rec)
    conn.close()
    return tokens, stamps, done


def _lanes_and_table(c, rng):
    """Positions within ``c["positions"]`` for ``c["lanes"]`` lanes, and a
    page table that hands each the pages its tokens need, out of order."""
    import numpy as np

    at = rng.randint(c["positions"][0], c["positions"][1] + 1,
                     size=c["lanes"])
    free = list(rng.permutation(np.arange(1, c["num_pages"])))
    table = np.zeros((c["lanes"], c["max_pages"]), np.int32)
    for lane, pos in enumerate(at):
        held = pos // c["page_size"] + 1
        table[lane, :held] = [free.pop() for _ in range(held)]
    return at, table


def paged_kernel_check(shapes, ctx, seed=0):
    """``_contrib_PagedAttention``'s kernel (compiled on a chip, interpreted
    elsewhere) against the XLA formulation from the same pool: largest gap
    of the attention's output, and the rows written."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import paged

    dev = ctx.jax_device()
    c = dict(shapes)
    rng = np.random.RandomState(seed + 2)
    at, table = _lanes_and_table(c, rng)
    kv_heads, dtype = c.get("kv_heads", c["heads"]), c.get("dtype", "float32")
    row = (c["lanes"], c["heads"], c["head_dim"])
    new = (c["lanes"], kv_heads, c["head_dim"])
    # float32 tokens by heads; bfloat16 tokens one row (ops/paged.py)
    plane = (c["num_pages"], c["page_size"]) + (
        (kv_heads, c["head_dim"]) if dtype == "float32"
        else (kv_heads * c["head_dim"],))
    ops = [jax.device_put(jnp.asarray(rng.randn(*shape), dtype), dev)
           for shape in (row, new, new, plane, plane)]
    ops += [jax.device_put(table, dev),
            jax.device_put(at.astype(np.int32), dev)]
    scale = 1.0 / math.sqrt(c["head_dim"])
    want = jax.jit(lambda *a: paged._gather_decode(*a, scale))(*ops)
    got = jax.jit(lambda *a: paged._kernel_decode(
        *a, scale, interpret=dev.platform != "tpu"))(*ops)
    gap = float(jnp.abs(got[0].astype(jnp.float32)
                        - want[0].astype(jnp.float32)).max())
    check({d for g in got for d in g.devices()} == {dev},
          "paged-attention kernel ran on %s" % dev)
    check(gap <= PAGED_TOL and all(
        bool(jnp.array_equal(g, w)) for g, w in zip(got[1:], want[1:])),
        "paged-attention kernel vs the XLA formulation at %d lanes, %d pages "
        "of %d, %d over %d x %d %s, table width %d, positions %d-%d: "
        "max|diff| = %.2e <= %.0e, written rows equal"
        % (c["lanes"], c["num_pages"], c["page_size"], c["heads"], kv_heads,
           c["head_dim"], dtype, c["max_pages"], at.min(), at.max(), gap,
           PAGED_TOL))
    return gap


def latent_kernel_check(shapes, ctx, seed=0):
    """``_contrib_PagedLatentAttention``'s kernel (compiled on a chip,
    interpreted elsewhere) against the XLA formulation from the same plane:
    largest gap of the attended rows over their largest value, and the rows
    written."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import paged

    dev = ctx.jax_device()
    c = dict(shapes)
    rng = np.random.RandomState(seed + 3)
    at, table = _lanes_and_table(c, rng)
    width = c["rank"] + c["rope"]
    plane = rng.randn(c["num_pages"], c["page_size"], c["row"])
    plane[..., width:] = 0  # as the pool holds a row (HybridLM.latent_row)
    ops = [jax.device_put(jnp.asarray(x, jnp.bfloat16), dev) for x in (
        rng.randn(c["lanes"], c["heads"], c["nope"]),
        rng.randn(c["lanes"], c["heads"], c["rope"]),
        rng.randn(c["lanes"], width),
        rng.randn(c["heads"] * (c["nope"] + c["v"]), c["rank"])
        / math.sqrt(c["rank"]), plane)]
    ops += [jax.device_put(table, dev),
            jax.device_put(at.astype(np.int32), dev)]
    scale = 1.0 / math.sqrt(c["nope"] + c["rope"]) / math.sqrt(c["nope"])
    want = paged.paged_latent_attention(*ops, scale=scale)
    got = paged._kernel_latent_decode(*ops, scale=scale,
                                      interpret=dev.platform != "tpu")
    gap = float(jnp.abs(got[0].astype(jnp.float32)
                        - want[0].astype(jnp.float32)).max()
                / jnp.abs(want[0].astype(jnp.float32)).max())
    check({d for g in got for d in g.devices()} == {dev},
          "latent-attention kernel ran on %s" % dev)
    check(gap <= PAGED_TOL and bool(jnp.array_equal(got[1], want[1])),
          "latent-attention kernel vs the XLA formulation at %d lanes, %d "
          "pages of %d, %d heads over rows of %d + %d in %d bfloat16, table "
          "width %d, positions %d-%d: max|diff| / max|XLA| = %.2e <= %.0e, "
          "written rows equal"
          % (c["lanes"], c["num_pages"], c["page_size"], c["heads"],
             c["rank"], c["rope"], c["row"], c["max_pages"], at.min(),
             at.max(), gap, PAGED_TOL))
    return gap


def lane_pick_check(eng, seed=0):
    """The decode lane program picks its own tokens and feeds them on
    (generation/engine.py, ``_plain_step``): on an idle engine, over pages
    of its own, the ids it picks are the host's argmax of the logits it
    returns, and a step fed on the device (``source``) returns bit for bit
    the logits of the same step fed from the host."""
    import numpy as np

    b = eng.max_lanes
    pred = eng._decode[b]
    rng = np.random.RandomState(seed + 2)
    sids = ["smoke-%d" % i for i in range(b)]
    for sid in sids:
        eng.pool.alloc(sid, 2)
    try:
        table = np.stack([eng.pool.page_table_row(sid, eng.max_pages)
                          for sid in sids]).astype(np.float32)
        ids = rng.randint(0, eng.vocab_size, size=b).astype(np.float32)
        at = np.zeros((b,), np.float32)
        logits = eng._run_lanes(pred, ids, at, table)
        picked = pred._exec.arg_dict["prev_ids"].asnumpy()
        check(np.array_equal(picked, logits.argmax(-1)),
              "the lane program's picks are the host's argmax of its "
              "logits: %s" % picked.astype(int).tolist())
        # position 1 twice over the same K/V: lane i takes lane b-1-i's pick
        back = np.arange(b, dtype=np.float32)[::-1].copy()
        fed = eng._dispatch_lanes(pred, np.zeros_like(ids), at + 1, table,
                                  source=back)[0].asnumpy()
        host = eng._run_lanes(pred, picked[::-1].copy(), at + 1, table)
        check(np.array_equal(fed, host),
              "a step fed on the device returns the logits of the step fed "
              "from the host, bit for bit")
    finally:
        for sid in sids:
            eng.pool.free(sid)


def scan_kernel_check(shapes, ctx, seed=0):
    """``_contrib_SSMScan``'s kernel (compiled on a chip, interpreted
    elsewhere) against the XLA formulation of the same bfloat16 rows:
    largest gap of ``y`` over the prompts' live rows and of the final
    state, each over the XLA form's largest value; every row finite."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import ssm

    dev = ctx.jax_device()
    c = dict(shapes)
    rng = np.random.RandomState(seed + 4)
    b, L, heads = len(c["lengths"]), c["L"], c["heads"]
    sizes = dict(heads=heads, head_dim=64, state=128, chunk=256)
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    ops = [jnp.asarray(silu(rng.randn(b, L, heads * 64 + 256)), jnp.bfloat16),
           jnp.asarray(rng.randn(b, L, heads) - 1.0, jnp.bfloat16),
           jnp.asarray(np.log(rng.uniform(1, 16, heads)), jnp.float32),
           jnp.asarray(rng.randn(heads), jnp.float32),
           jnp.asarray(rng.randn(heads), jnp.float32),
           jnp.asarray(c["lengths"], jnp.int32)]
    ops = [jax.device_put(x, dev) for x in ops]
    want = jax.jit(lambda *a: ssm.ssm_scan(*a, **sizes))(*ops)
    got = jax.jit(lambda *a: ssm.ssm_scan(
        *a, scan=functools.partial(ssm._kernel_scan,
                                   interpret=dev.platform != "tpu"),
        **sizes))(*ops)
    live = np.arange(L)[None, :, None] < np.asarray(c["lengths"])[:, None,
                                                                  None]
    y, y_want = (np.asarray(a[0], np.float32) for a in (got, want))
    y_gap = float(np.abs(np.where(live, y - y_want, 0)).max()
                  / np.abs(y_want).max())
    s_gap = float(jnp.abs(got[1] - want[1]).max() / jnp.abs(want[1]).max())
    check({d for g in got for d in g.devices()} == {dev},
          "scan kernel ran on %s" % dev)
    check(y_gap <= SCAN_Y_TOL and s_gap <= SCAN_STATE_TOL
          and np.isfinite(y).all(),
          "scan kernel vs the XLA formulation at %d prompts of %s in a "
          "bucket of %d, %d heads of 64, state 128, chunks of 256, bfloat16: "
          "max|diff| / max|XLA| of y = %.2e <= %.0e, of the state = %.2e <= "
          "%.0e, every row finite"
          % (b, "/".join(map(str, c["lengths"])), L, heads, y_gap,
             SCAN_Y_TOL, s_gap, SCAN_STATE_TOL))
    return y_gap, s_gap


def server_phase(cfg, ctx, seed=0):
    """InferenceServer + generator on ``ctx`` behind its HTTP endpoint:
    >=4 ``POST /generate`` requests, two in flight at a time; the first
    prompt is sent twice.  Then the prefill logits of that prompt from a
    ``Predictor`` on ``ctx`` against the same one bound on ``mx.cpu()``, and
    the paged-attention kernel against the XLA formulation at the decode
    cell's shapes (``cfg["paged"]`` overrides them).
    Returns {"transcripts", "step_ms", "tokens_per_s", "logits_rel_diff",
    "paged_kernel_gap", "latent_kernel_gap", "scan_kernel_gaps", "devices"}."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import get_transformer_lm_prefill

    dev = ctx.jax_device()
    net, params = make_lm_params(cfg, ctx, seed)
    spec = dict(
        vocab_size=cfg["vocab"], num_layers=cfg["layers"],
        num_heads=cfg["heads"], hidden=cfg["hidden"],
        max_seq_len=cfg["max_seq"], lane_buckets=(1, 2, cfg["lanes"]),
        page_size=cfg["page_size"],
        num_pages=cfg["lanes"] * cfg["max_seq"] // cfg["page_size"],
        prefill_len_buckets=cfg["prompt_lens"],
        prefill_batch_buckets=(1, 2))
    t0 = time.perf_counter()
    srv = mx.serving.InferenceServer(
        net, params,
        {"data": (2, cfg["max_seq"]), "softmax_label": (2, cfg["max_seq"])},
        ctx=ctx, generator_spec=spec)
    try:
        say("server built and warmed (every prefill/decode/scoring bucket "
            "compiled) in %.1f s" % (time.perf_counter() - t0))
        eng = srv.generator
        where = eng.devices()
        check(all(v == [str(dev)] for v in where.values()),
              "generator weights, KV pool, prefill and decode outputs live on "
              "%s: %s"
              % (dev, where))
        host, port = srv.serve_http()
        rng = np.random.RandomState(seed + 1)
        prompts = [[int(t) for t in rng.randint(0, cfg["vocab"], size=n)]
                   for n in cfg["prompt_lens"]]
        prompts.append(prompts[0])  # the same prompt again
        results = [None] * len(prompts)
        errors = []

        def client(i):
            try:
                results[i] = _post_generate(host, port, prompts[i],
                                            cfg["new_tokens"])
            except BaseException as exc:  # re-raised below, never dropped
                errors.append(exc)

        steps0 = eng.metrics.steps.value
        t0 = time.perf_counter()
        for pair in range(0, len(prompts), 2):  # two in flight together
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(pair, min(pair + 2, len(prompts)))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        steps = eng.metrics.steps.value - steps0
        transcripts = [r[0] for r in results]
        total = sum(len(t) for t in transcripts)
        check(all(len(t) == cfg["new_tokens"]
                  and all(0 <= tok < cfg["vocab"] for tok in t)
                  for t in transcripts),
              "%d /generate requests each returned %d in-range tokens"
              % (len(prompts), cfg["new_tokens"]))
        check(transcripts[0] == transcripts[-1],
              "the same prompt twice gives the same transcript")
        check(steps < total, "continuous batching: %d decode steps for %d "
              "tokens" % (steps, total))
        gaps = sorted(b - a for _, st, _ in results
                      for a, b in zip(st, st[1:]))
        step_ms = gaps[len(gaps) // 2] * 1e3
        say("decode: median inter-token gap %.1f ms (the KV pool stays on "
            "the device; paged attention: %s), %.2f tokens/s over %d tokens, "
            "%d steps, ttft_ms %s"
            % (step_ms, eng.snapshot()["paged_attention"], total / wall,
               total, steps, ["%.0f" % r[2]["ttft_ms"] for r in results]))
        snap = eng.snapshot()
        check(0 < snap["steps_overlapped"] <= snap["steps"]
              and snap["tokens_dropped"] == 0,
              "one step in flight: %d of %d steps dispatched before the "
              "step ahead was read, %d tokens dropped"
              % (snap["steps_overlapped"], snap["steps"],
                 snap["tokens_dropped"]))
        lane_pick_check(eng, seed)
    finally:
        srv.stop()
    paged_gap = paged_kernel_check(cfg.get("paged", PAGED), ctx, seed)
    for shapes in cfg.get("paged_grouped", PAGED_GROUPED):
        paged_kernel_check(shapes, ctx, seed)
    latent_gap = latent_kernel_check(cfg.get("paged_latent", PAGED_LATENT),
                                     ctx, seed)
    scan_gaps = [scan_kernel_check(shapes, ctx, seed)
                 for shapes in cfg.get("ssm_scan", SSM_SCAN)]

    # prefill logits: ctx vs an explicit mx.cpu() bind — a named
    # comparison, not a fallback
    L = cfg["prompt_lens"][0]
    symbol = get_transformer_lm_prefill(
        cfg["vocab"], cfg["layers"], cfg["heads"], cfg["hidden"], seq_len=L,
        max_seq_len=cfg["max_seq"])
    feed = np.asarray(prompts[0], np.float32)[None]
    logits = {}
    for name, c in (("chip", ctx), ("cpu", mx.cpu())):
        pred = mx.Predictor(symbol, params, {"data": (1, L)}, ctx=c)
        out = pred.forward(data=feed)[0]
        check(_devices_of([out]) == [c.jax_device()],
              "%s prefill logits computed on %s" % (name, c.jax_device()))
        logits[name] = out.asnumpy().astype(np.float32)
    check(logits["chip"].shape[-1] == cfg["vocab"]
          and np.isfinite(logits["chip"]).all(),
          "prefill logits finite, shape %s" % (logits["chip"].shape,))
    rel = float(np.abs(logits["chip"] - logits["cpu"]).max()
                / np.abs(logits["cpu"]).max())
    check(rel <= LOGITS_REL_TOL,
          "prefill logits on %s vs explicit mx.cpu(): max|diff|/max|cpu| = "
          "%.2e <= %.0e" % (dev, rel, LOGITS_REL_TOL))
    return {"transcripts": transcripts, "step_ms": step_ms,
            "tokens_per_s": total / wall, "logits_rel_diff": rel,
            "paged_kernel_gap": paged_gap, "latent_kernel_gap": latent_gap,
            "scan_kernel_gaps": scan_gaps,
            "devices": where}


# ---------------------------------------------------------------------------
# the path across chips
# ---------------------------------------------------------------------------

def dp_phase(cfg, contexts, seed=0):
    """The LM trained data-parallel over ``contexts`` (one sequence per
    chip) against the one-chip run of the same seeded batch."""
    one = trainer_phase(cfg, contexts[:1], seed)
    gc.collect()  # the one-chip step's buffers nearly fill chip 0
    dp = trainer_phase(cfg, contexts, seed)
    say("dp%d parameters replicated on device ids %s; batch split over %s"
        % (len(contexts), [d.id for d in dp["param_devices"]],
           [d.id for d in dp["data_devices"]]))
    check(len({d.id for d in dp["data_devices"]}) == len(contexts),
          "%d distinct devices used" % len(contexts))
    diffs = [abs(a - b) for a, b in zip(one["losses"], dp["losses"])]
    check(max(diffs) <= DP_LOSS_TOL,
          "dp%d losses %s match one-chip %s within %.2f (max diff %.4f)"
          % (len(contexts), ["%.4f" % l for l in dp["losses"]],
             ["%.4f" % l for l in one["losses"]], DP_LOSS_TOL, max(diffs)))
    return {"one": one, "dp": dp}


# ---------------------------------------------------------------------------

def run(chips, seed):
    import jax

    import mxnet_tpu as mx

    cache_dir, before = _cache_entries()
    say("compile cache: %s (%s), %d entries before"
        % (cache_dir, "JAX_COMPILATION_CACHE_DIR" if
           "JAX_COMPILATION_CACHE_DIR" in os.environ else "set by mxnet_tpu",
           before))
    say("default context here: %s" % mx.current_context())
    t0 = time.perf_counter()
    if chips == 1:
        trainer_phase(TRAIN, [mx.tpu(0)], seed)
        t1 = time.perf_counter()
        say("trainer phase %.1f s" % (t1 - t0))
        gc.collect()  # the trainer's buffers nearly fill the chip
        server_phase(SERVE, mx.tpu(0), seed)
        say("server phase %.1f s" % (time.perf_counter() - t1))
    else:
        check(len(jax.devices()) >= chips, "%d chips attached" % chips)
        dp_phase(dict(TRAIN, batch=chips),
                 [mx.tpu(i) for i in range(chips)], seed)
        say("dp phase %.1f s" % (time.perf_counter() - t0))
    say("compile cache: %d entries after (%d before)"
        % (_cache_entries()[1], before))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the data-parallel trainer over four "
                         "chips against the one-chip run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    ok, devices = False, []
    try:
        import jax

        devices = jax.devices()
        say("jax %s, devices: %s" % (jax.__version__, devices))
        if devices[0].platform != "tpu":
            raise RuntimeError(
                "no TPU: jax.devices()[0].platform is %r — this smoke "
                "never runs the model on the host" % devices[0].platform)
        run(args.chips, args.seed)
        ok = True
    except BaseException:
        traceback.print_exc()
        say("FAILED")
    # the contracted line is the last thing standard output ever carries:
    # whatever an exit hook or a library writes later goes to stderr
    sys.stdout.write(final_line(ok, devices) + "\n")
    sys.stdout.flush()
    os.dup2(2, 1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
