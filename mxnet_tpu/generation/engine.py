"""DecodeEngine — iteration-level continuous batching over paged KV.

Autoregressive serving has two phases with opposite shapes: *prefill*
(one big parallel pass over the prompt) and *decode* (one token per
sequence per step, forever).  Request-level batching couples both to
the slowest member of a batch; iteration-level ("continuous") batching
instead re-forms the batch EVERY decode step — new sequences are
admitted into free lanes the moment prefill finishes, finished ones
retire immediately — so short requests never wait for long ones and
the decode executable stays saturated (Orca / vLLM, PAPERS.md).

Two token-path optimizations ride on top of the paged pool:

* **Cross-request prefix caching** (``prefix_cache_pages`` /
  ``MXNET_GEN_PREFIX_CACHE_PAGES``): admission resolves the prompt
  against :class:`~.kv_pool.PagedKVPool`'s content-hash prefix index.
  A fully-cached prompt skips prefill entirely — the sequence enters
  decode with ``next_pos`` pointing at its LAST prompt position, so
  TTFT collapses to ONE engine iteration.  Pages are refcounted and
  copy-on-write: before any write into a potentially shared page the
  engine calls ``ensure_writable``.  Every complete page a sequence
  materializes is re-published (``register_prefix``), which also makes
  preemption cheap: the re-admitted sequence finds its own pages in
  the index instead of re-prefilling prompt+generated from scratch.

* **Speculative decoding** (``draft=``): a small draft model proposes
  K tokens per iteration (its own paged pool + decode executables),
  then ONE windowed target pass — the same teacher-forcing graph as
  prefix catch-up (``models.transformer.get_transformer_lm_catchup``),
  since every feed token is known before the call — scores all K+1
  slots in a single causal forward.  Greedy acceptance keeps every
  token whose draft matched the target argmax, so transcripts match
  non-speculative greedy (asserted per-K by the spec-parity tests).  K is
  resolved at construction (the draft spec's ``k``, else
  ``MXNET_GEN_DRAFT_K``) and travels inside ``spec()`` / AOT bundles; the
  acceptance-rate EWMA is reported by ``snapshot()`` and the
  ``mxtpu_gen_draft_accept_rate`` gauge.

XLA discipline: every XLA-visible shape here is static.

* Prefill runs through one :class:`~mxnet_tpu.serving.batcher.
  BucketedPredictor` per prompt-length bucket (pow2 lengths), i.e. the
  same shape-quantized executables the scoring tier uses.
* Decode is a fixed-lane slotted program (``models.transformer.
  get_transformer_lm_decode``): ``lanes`` sequences advance one token
  through per-lane page tables into a shared paged KV pool
  (:mod:`.kv_pool`) whose planes stay on the device — every lane
  program binds them as carried arguments and updates them in place;
  a step sends ids, positions and page tables up and reads ``lanes``
  picked ids down, one iteration late (the plain step keeps one step in
  flight: see ``_plain_step``) — compiled ONCE per lane-count bucket and
  primed through the PR 10 compile cache (entry kinds ``gen-step`` /
  ``gen-prefill`` / ``gen-verify`` / ``gen-draft-step`` /
  ``gen-draft-prefill``), so AOT bundles restore a generate-ready
  replica with zero cold compiles.

The family seam: the engine builds no graph itself.  It asks a *model
family* object (``models.TransformerLMFamily``, ``models.HybridLM``;
``models.generator_family`` resolves the engine's keywords to one, here
and for ``platform.ModelSpec.kv_footprint``) for the prefill
graph of a length bucket (``prefill_symbol``; ``prefill_inputs`` names its
per-prompt inputs, ``data`` and for some ``length``), the decode graph
(``decode_symbol``), the windowed catch-up / verify graph or ``None``
(``catchup_symbol``) and the planes the lane programs carry (``planes()``:
name, kind ``paged`` or ``slot``, entry shape, dtype), which one
:class:`~.kv_pool.PagedKVPool` owns, and the keys it adds to :meth:`spec`
(``engine_spec()``).  A family with slot planes (recurrent
state, convolution tails, a sliding-window layer's ring) gets a
``state_slot`` vector beside ``page_table``; one with rings says what a lane
holds of them (``ring_bytes()``: ``gen:step``'s ``window_bytes``).  A family
may name small outputs its lane program returns after the picked ids (``lane_extras``; ``expert_load``, the live lanes'
picks by expert layer and expert, is the one the engine knows what to do
with): they are read with the ids, one iteration late.  What a family
without a catch-up graph cannot do is refused by name, never done wrongly:
``draft=`` and ``prefix_cache_pages > 0`` raise at construction (a
recurrent state cannot be rewound past a rejected token, nor rebuilt from
cached pages), and a preempted sequence is re-admitted by a prefill over
its whole transcript, or fails with :class:`StateNotRebuildableError` where
that is longer than the largest prefill bucket.  Ids, positions, sources,
slots and tables travel in a carrier dtype of their own (float32: exact for
ids below 2**24), whatever the dtype of weights and planes.

Backpressure: admission is a bounded pending queue (reject =
:class:`~mxnet_tpu.serving.batcher.QueueFullError`, the HTTP 429/503
contract) plus KV-pool capacity; a mid-decode pool exhaustion preempts
the youngest lane (its pages are freed — though complete ones stay in
the prefix index — and the sequence re-queues for re-admission of
prompt+generated; greedy decode is deterministic, so the stream
continues seamlessly), which bounds memory without ever deadlocking.
"""
from __future__ import annotations

import functools
import json
import logging
import queue
import threading
import time
from collections import deque, namedtuple
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import faults
from .. import telemetry as _telemetry
from ..profiler import Frame as _span, first_call as _first_call, \
    framed as _framed, leaves_bytes as _leaves_bytes
from ..base import MXNetError, env, register_env
from ..serving.batcher import (BucketedPredictor, DeadlineExceededError,
                               QueueFullError, ServerClosedError,
                               pow2_buckets)
from ..ops.moe import experts_formulation, experts_path
from ..ops.paged import (LATENT_PREFILL, WINDOW_STEP, decode_formulation,
                         latent_formulation, sequence_formulation)
from ..ops.ssm import scan_formulation, step_formulation
from .kv_pool import KVPoolExhaustedError, PagedKVPool

__all__ = ["DecodeEngine", "GenStream", "StateNotRebuildableError"]

# what ids, positions, sources, slots and page tables are fed as
_CARRIER = np.dtype(np.float32)


def _state_step(symbol, pool):
    """``(head_dim, state, plane dtype)`` of a lane graph's
    ``_contrib_SSMStep`` nodes (its first: a family's state-space layers
    are of one shape), what ``ops/ssm.py`` ``step_formulation`` picks from;
    None for a graph without one."""
    nodes = json.loads(symbol.tojson())["nodes"]
    dtypes = {s.name: s.dtype for s in pool.specs}
    return next(((int(n["attr"]["head_dim"]), int(n["attr"]["state"]),
                  dtypes[nodes[n["inputs"][5][0]]["name"]])
                 for n in nodes if n["op"] == "_contrib_SSMStep"), None)


def _state_scan(symbol, params):
    """``heads``, ``head_dim``, ``state``, ``chunk`` and ``dtype`` of a
    prefill graph's ``_contrib_SSMScan`` nodes (its first: a family's
    state-space layers are of one shape; the dtype is that of the
    convolution's weight before it, which writes the rows the scan reads):
    what ``ops/ssm.py`` ``scan_formulation`` picks from beside the bucket;
    None for a graph without one."""
    nodes = json.loads(symbol.tojson())["nodes"]
    for n in nodes:
        if n["op"] == "_contrib_SSMScan":
            conv = nodes[n["inputs"][0][0]]
            weight = params[nodes[conv["inputs"][1][0]]["name"]]
            sizes = {k: int(n["attr"][k])
                     for k in ("heads", "head_dim", "state")}
            return dict(sizes, chunk=int(n["attr"].get("chunk", 256)),
                        dtype=np.dtype(weight.dtype))
    return None


def _sequence_heads(symbol, family):
    """Query heads of a prefill graph's grouped-query sequence attention
    (its ``_contrib_WindowAttention`` nodes' where it has them, else its
    ``_contrib_DenseAttention`` nodes' over fewer K/V heads), what
    ``ops/paged.py`` ``sequence_formulation`` picks from beside the bucket
    and the family's K/V heads; None for a graph without one."""
    ops = {n["op"] for n in json.loads(symbol.tojson())["nodes"]}
    if "_contrib_WindowAttention" in ops:
        return family.window_heads
    if "_contrib_DenseAttention" in ops \
            and family.kv_heads != family.num_heads:
        return family.num_heads
    return None


def _expert_products(symbol, params):
    """``(leaves' dtype, hidden, expert width)`` of a lane graph's
    ``_contrib_RoutedExperts`` nodes (its first: a family's expert layers
    are of one shape), what ``ops/moe.py`` ``experts_formulation`` picks
    from; None for a graph without one."""
    nodes = json.loads(symbol.tojson())["nodes"]
    leaves = (params[nodes[n["inputs"][3][0]]["name"]] for n in nodes
              if n["op"] == "_contrib_RoutedExperts")
    return next(((np.dtype(w13.dtype), w13.shape[1], w13.shape[2] // 2)
                 for w13 in leaves), None)


def _latent_rows(symbol, params, pool):
    """``(rank, the plane's row, plane dtype)`` of a lane graph's
    ``_contrib_PagedLatentAttention`` nodes (its first: a family's latent
    layers are of one shape), what ``ops/paged.py`` ``latent_formulation``
    picks from beside the heads; None for a graph without one."""
    nodes = json.loads(symbol.tojson())["nodes"]
    planes = {s.name: s for s in pool.specs}
    for n in nodes:
        if n["op"] == "_contrib_PagedLatentAttention":
            weight, plane = (nodes[n["inputs"][i][0]]["name"] for i in (3, 4))
            return (params[weight].shape[-1], planes[plane].shape[-1],
                    planes[plane].dtype)
    return None


class StateNotRebuildableError(MXNetError):
    """A preempted sequence of a recurrent family whose transcript is longer
    than the largest prefill bucket: its state is gone and no graph the
    engine has can rebuild it."""


register_env("MXNET_GEN_PAGE_SIZE", 16, int,
             "KV-pool page size (tokens per page) for DecodeEngine.")
register_env("MXNET_GEN_NUM_PAGES", 128, int,
             "KV-pool page count (page 0 is reserved scratch) for "
             "DecodeEngine.")
register_env("MXNET_GEN_MAX_LANES", 8, int,
             "Largest decode lane-count bucket (max sequences advancing "
             "per decode step).")
register_env("MXNET_GEN_MAX_NEW_TOKENS", 64, int,
             "Default generation budget when a request does not say.")
register_env("MXNET_GEN_PENDING_QUEUE", 256, int,
             "Bounded admission queue for DecodeEngine.submit; beyond it "
             "submissions raise QueueFullError (HTTP 429).")
register_env("MXNET_GEN_PREFIX_CACHE_PAGES", 0, int,
             "Max refcount-0 KV pages the cross-request prefix index may "
             "retain (LRU-evicted); 0 disables prefix caching.")
register_env("MXNET_GEN_DRAFT_K", 4, int,
             "Speculative draft length (tokens proposed per iteration) "
             "when a draft model is configured without an explicit K.")

_DONE = object()  # GenStream queue sentinel


class GenStream:
    """One request's streaming handle: iterate tokens as they decode.

    ``for tok in stream`` yields generated token ids incrementally;
    :meth:`result` blocks for the full list.  ``ttft_ms`` / ``itl_ms``
    expose this request's observed first-token latency and inter-token
    gaps once available.  Token-path introspection: ``prefill_tokens``
    (prompt positions actually prefilled, across re-admissions),
    ``cached_prefix_tokens`` (positions served from the prefix cache),
    ``ttft_iters`` (engine iterations before the first token — 0 when
    prefill itself emitted it, 1 for a fully-cached prompt),
    ``draft_proposed`` / ``draft_accepted`` / ``accept_rate`` (per-
    stream speculative acceptance EWMA)."""

    def __init__(self, prompt, max_new_tokens):
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        # the engine's id for this request, set at submit: what its spans
        # (``gen:queued``, ``gen:step``'s ``sids``, ``serve:generate``) share
        self.sid: Optional[int] = None
        self.tokens: List[int] = []
        self.ttft_ms: Optional[float] = None
        self.itl_ms: List[float] = []
        self.prefill_tokens = 0
        self.cached_prefix_tokens = 0
        self.ttft_iters: Optional[int] = None
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.accept_rate: Optional[float] = None
        self._t0 = time.monotonic()
        self._t_last = None
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._exc: Optional[BaseException] = None

    # -- engine side ------------------------------------------------------
    def _emit(self, token: int) -> float:
        """Record one generated token; returns the gap (ms) it observed
        (TTFT for the first token, ITL after)."""
        now = time.monotonic()
        if self._t_last is None:
            gap = (now - self._t0) * 1e3
            self.ttft_ms = gap
        else:
            gap = (now - self._t_last) * 1e3
            self.itl_ms.append(gap)
        self._t_last = now
        self.tokens.append(int(token))
        self._q.put(int(token))
        return gap

    def _finish(self, exc: Optional[BaseException] = None):
        if self._done.is_set():
            return
        self._exc = exc
        self._done.set()
        self._q.put(_DONE)

    # -- consumer side ----------------------------------------------------
    @property
    def done(self) -> bool:
        return self._done.is_set()

    def exception(self) -> Optional[BaseException]:
        return self._exc

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _DONE:
                if self._exc is not None:
                    raise self._exc
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("generation still running")
        if self._exc is not None:
            raise self._exc
        return list(self.tokens)


class _Seq:
    """Engine-internal live-sequence state (one decode lane's occupant).

    ``next_pos`` is the feed cursor: the position whose token goes into
    the NEXT decode/verify slot (every position below it has final K/V
    materialized in the pool, or will have by the step in flight).  It
    advances when a step is dispatched: steady state keeps ``next_pos ==
    len(tokens) - 1`` once that step is read and ``len(tokens)`` while it
    is in flight (the token to feed is then still on the device); a
    cached-prefix admission starts it at the hit
    length, a partial hit or a re-admitted preemptee walks the known
    suffix forward one slot per step without emitting.  ``draft_pos``
    is the same cursor for the draft model's pool; ``limit`` is
    ``len(prompt) + max_new`` — no position at or beyond it is ever
    fed, so pool allocations never outgrow the admission-time check."""

    __slots__ = ("sid", "stream", "tokens", "gen_count", "max_new",
                 "deadline", "eos_id", "admitted_at", "next_pos",
                 "draft_pos", "iters", "limit")

    def __init__(self, sid, stream, deadline, eos_id):
        self.sid = sid
        self.stream = stream
        self.tokens = list(stream.prompt)  # prompt + generated so far
        self.gen_count = len(stream.tokens)
        self.max_new = stream.max_new_tokens
        self.deadline = deadline  # absolute monotonic seconds or None
        self.eos_id = eos_id
        self.admitted_at = 0.0
        self.next_pos = 0
        self.draft_pos = 0
        self.iters = 0
        self.limit = len(stream.prompt) + self.max_new


# A decode step that was dispatched and not yet read: its executable, its
# lanes as ``(sequence, position fed)`` and the device array of the ids it
# picks.
# ``extras``: what the lane program returned after the ids (the family's
# ``lane_extras``), unread, by name
_Flight = namedtuple("_Flight", "pred lanes ids extras")


class _GenMetrics:
    """Telemetry collector for one engine: token throughput, TTFT/ITL
    histograms, admission/retire/preempt counters, lane occupancy, and
    the speculative-decoding draft economy."""

    def __init__(self):
        reg = self._registry = _telemetry.Registry()
        self.tokens = reg.counter("mxtpu_gen_tokens_total")
        self.admitted = reg.counter("mxtpu_gen_sequences_admitted_total")
        self.retired = reg.counter("mxtpu_gen_sequences_retired_total")
        self.preempted = reg.counter("mxtpu_gen_sequences_preempted_total")
        self.expired = reg.counter("mxtpu_gen_sequences_expired_total")
        self.rejected = reg.counter("mxtpu_gen_sequences_rejected_total")
        self.failed = reg.counter("mxtpu_gen_sequences_failed_total")
        self.steps = reg.counter("mxtpu_gen_decode_steps_total")
        # steps dispatched before the step ahead of them was read, and
        # tokens such a step computed for a lane that had retired meanwhile
        self.steps_overlapped = reg.counter(
            "mxtpu_gen_decode_steps_overlapped_total")
        self.tokens_dropped = reg.counter("mxtpu_gen_tokens_dropped_total")
        self.cold_steps = reg.counter("mxtpu_gen_decode_cold_steps_total")
        self.cached_admissions = reg.counter(
            "mxtpu_gen_prefix_cached_admissions_total")
        self.draft_proposed = reg.counter("mxtpu_gen_draft_proposed_total")
        self.draft_accepted = reg.counter("mxtpu_gen_draft_accepted_total")
        self.spec_fallbacks = reg.counter("mxtpu_gen_spec_fallbacks_total")
        # 0.5ms .. ~16s exponential buckets
        self.ttft = reg.histogram("mxtpu_gen_ttft_ms")
        self.itl = reg.histogram("mxtpu_gen_itl_ms")
        self.g_active = reg.gauge("mxtpu_gen_active_lanes")
        self.g_pending = reg.gauge("mxtpu_gen_pending_requests")
        self.g_accept = reg.gauge("mxtpu_gen_draft_accept_rate")
        # a family with routed experts: the live lanes' picks, and the
        # experts with at least one pick in the last step read (summed over
        # the expert layers)
        self.expert_picks = reg.counter("mxtpu_gen_expert_picks")
        self.g_experts_hit = reg.gauge("mxtpu_gen_experts_hit")
        # the picks of the last step read that landed on experts held here:
        # the pairs the grouped products multiply
        self.g_expert_pairs_held = reg.gauge("mxtpu_gen_expert_pairs_held")
        # a family with latent attention: the latent rows the last step
        # dispatched had to read (live tokens x a token's bytes over the
        # latent planes)
        self.g_latent_bytes = reg.gauge("mxtpu_gen_latent_bytes")
        # a family with sliding-window layers: the rings the last step
        # dispatched had to fetch (live lanes x a lane's rings)
        self.g_window_bytes = reg.gauge("mxtpu_gen_window_bytes")
        _telemetry.register_collector(self)

    def render_prometheus(self):
        return self._registry.render_prometheus()


class DecodeEngine:
    """Continuous-batching generation over a decoder-only LM checkpoint.

    Parameters
    ----------
    params : dict | str
        ``{name: array}`` (``arg:`` prefixes allowed) or a ``.params``
        path — the family's checkpoint (``get_transformer_lm``'s for the
        default family); all prefill/decode executors share one copy of
        the weights, bound in the dtype they come in.
    vocab_size, num_layers, num_heads, hidden
        The default family's geometry (``models.TransformerLMFamily``;
        must match the checkpoint); not used with ``family``.
    family : object | dict, optional
        The model family (module docstring, "The family seam"), or its
        ``spec()``.
    max_seq_len : int
        Positions a sequence may reach (prompt + generated).
    lane_buckets : sequence of int, optional
        Decode lane-count buckets (default ``pow2_buckets(
        MXNET_GEN_MAX_LANES)``); one executable per bucket.
    page_size, num_pages : int, optional
        KV-pool geometry (``MXNET_GEN_PAGE_SIZE`` / ``_NUM_PAGES``).
    prefill_len_buckets, prefill_batch_buckets
        Prompt-length and prefill-batch shape quantization; one
        :class:`BucketedPredictor` per length bucket.
    eos_id : int, optional
        Token id that ends a sequence early.
    prefix_cache_pages : int, optional
        Cross-request prefix-cache retention bound (refcount-0 pages
        the index may keep); default ``MXNET_GEN_PREFIX_CACHE_PAGES``,
        0 disables caching entirely (legacy semantics).
    draft : dict, optional
        Speculative-decoding draft model: ``{"params": path-or-dict,
        "num_layers": int, "num_heads": int, "hidden": int,
        "k": int or None}``.  ``k`` None takes ``MXNET_GEN_DRAFT_K``;
        the RESOLVED value is stored back into :meth:`spec` so
        bundles/replicas rebuild with the same K.
    """

    @_framed("start:engine")
    def __init__(self, params, vocab_size=None, num_layers=4, num_heads=8,
                 hidden=512, max_seq_len=128,
                 lane_buckets: Optional[Sequence[int]] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefill_len_buckets: Optional[Sequence[int]] = None,
                 prefill_batch_buckets: Sequence[int] = (1, 2, 4),
                 eos_id: Optional[int] = None,
                 max_pending: Optional[int] = None,
                 prefix_cache_pages: Optional[int] = None,
                 draft: Optional[Dict] = None,
                 ctx=None, dtype=np.float32, warmup: bool = True,
                 start: bool = True, family=None):
        from .. import ndarray as nd
        from ..models import generator_family
        from ..predictor import Predictor, on_ctx

        # ``dtype`` is the default family's planes'; the feeds' is _CARRIER
        self._dtype = np.dtype(dtype)
        if family is None and vocab_size is None:
            raise MXNetError("DecodeEngine needs vocab_size or family")
        self.family = family = generator_family(
            family, vocab_size, num_layers, num_heads, hidden, self._dtype)
        self.vocab_size = family.vocab_size
        # the family's geometry (spec(), snapshot())
        self.num_layers = family.num_layers
        self.num_heads = family.num_heads
        self.hidden = family.hidden
        self.max_seq_len = int(max_seq_len)
        self.head_dim = family.head_dim
        self.eos_id = eos_id
        # None: the current context — the chip when one is attached
        # (docs/how_to/deviations.md "Default context")
        from ..context import current_context

        self._ctx = ctx = ctx or current_context()
        self._device = ctx.jax_device()
        self.page_size = int(env("MXNET_GEN_PAGE_SIZE", 16, int)
                             if page_size is None else page_size)
        self.num_pages = int(env("MXNET_GEN_NUM_PAGES", 128, int)
                             if num_pages is None else num_pages)
        self.max_pages = -(-self.max_seq_len // self.page_size)
        self.lane_buckets = tuple(sorted(set(
            int(b) for b in (lane_buckets if lane_buckets is not None
                             else pow2_buckets(
                                 env("MXNET_GEN_MAX_LANES", 8, int))))))
        self.max_lanes = self.lane_buckets[-1]
        if prefill_len_buckets is None:
            prefill_len_buckets = [b for b in pow2_buckets(self.max_seq_len)
                                   if b >= min(8, self.max_seq_len)]
        self.prefill_len_buckets = tuple(sorted(set(
            int(b) for b in prefill_len_buckets)))
        self.prefill_batch_buckets = tuple(sorted(set(
            int(b) for b in prefill_batch_buckets)))
        self.max_pending = int(env("MXNET_GEN_PENDING_QUEUE", 256, int)
                               if max_pending is None else max_pending)
        self.default_max_new = env("MXNET_GEN_MAX_NEW_TOKENS", 64, int)
        self.prefix_cache_pages = max(0, int(
            env("MXNET_GEN_PREFIX_CACHE_PAGES", 0, int)
            if prefix_cache_pages is None else prefix_cache_pages))

        # what this family has no graph for is refused here, by name
        if (draft or self.prefix_cache_pages) and family.catchup_symbol(
                self.max_seq_len, self.page_size) is None:
            raise MXNetError(
                "the %s family has no windowed (catch-up / verify) graph: "
                "%s needs one. A recurrent state (or a sliding window's "
                "ring) cannot be rewound past a rejected token, nor rebuilt "
                "from cached pages"
                % (family.name, "draft=" if draft
                   else "prefix_cache_pages=%d" % self.prefix_cache_pages))

        # -- speculative draft config (resolve K once, here) --------------
        self._draft: Optional[Dict] = None
        self._draft_params = None
        self._verify_width = 1
        if draft:
            d = dict(draft)
            d_layers = int(d.get("num_layers", max(1, self.num_layers // 2)))
            d_heads = int(d.get("num_heads", self.num_heads))
            d_hidden = int(d.get("hidden", self.hidden))
            k = d.get("k")
            if k is None:
                k = env("MXNET_GEN_DRAFT_K", 4, int)
            k = max(1, min(int(k), self.max_seq_len - 1))
            dparams = d.get("params")
            self._draft = {"params": dparams, "num_layers": d_layers,
                           "num_heads": d_heads, "hidden": d_hidden,
                           "k": k}
            if isinstance(dparams, str):
                dparams = nd.load(dparams)
            if dparams is None:
                raise MXNetError("draft spec needs 'params'")
            self._draft_params = {k: on_ctx(v, ctx)
                                  for k, v in dparams.items()}
            self._verify_width = k + 1
        self._accept_ewma: Optional[float] = None

        if isinstance(params, str):
            params = nd.load(params)
        # one shared copy of the weights ON the engine's device: Predictor
        # passes live NDArrays of its own context through, so every
        # bucket executor binds the same arrays
        with _span("start:params", "startup") as span:
            self._params = {k: on_ctx(v, ctx) for k, v in params.items()}
            span.set(**_leaves_bytes(self._params.values()))

        # one manager for every plane the lane programs carry: K/V pages,
        # and a slot a lane (+ scratch) of each state plane
        self.pool = PagedKVPool(self.num_pages, self.page_size,
                                planes=family.planes(),
                                num_slots=self.max_lanes + 1,
                                prefix_cache_pages=self.prefix_cache_pages,
                                ctx=ctx)
        self.metrics = _GenMetrics()

        # prefill: one BucketedPredictor per prompt-length bucket.
        # Symbols build under a fresh NameManager so auto-generated op
        # names — and with them symbol.tojson(), the compile-cache graph
        # fingerprint — are independent of process construction history:
        # an engine restored from an AOT bundle must re-derive the same
        # digests the bundle was saved under.
        from ..name import NameManager

        def prefill_rig(fam, params, kind, name):
            rig = {}
            for L in self.prefill_len_buckets:
                with NameManager():
                    symbol = fam.prefill_symbol(L, self.max_seq_len)
                items = {"data": (L,), "length": ()}
                bp = BucketedPredictor(
                    symbol, params, {k: items[k] for k in fam.prefill_inputs},
                    self.prefill_batch_buckets, ctx=ctx, dtype=_CARRIER)
                for pred in bp._preds.values():
                    pred._exec._cache_kind = kind
                    pred._exec._program_name = name % L
                rig[L] = bp
            return rig

        def lane_rig(fam, params, pool, kind, name, width=None):
            """One fixed-lane Predictor per lane bucket (shared weights via
            reshape; pool shapes are lane-independent): the family's
            decode graph, or with ``width`` its windowed one.  Every one
            binds the pool's own planes, carried: the step updates them in
            place and nothing uploads or reads them."""
            with NameManager():
                symbol = (fam.decode_symbol if width is None
                          else fam.catchup_symbol)(self.max_seq_len,
                                                   self.page_size)

            def feeds(b):
                shape = (b,) if width is None else (b, width)
                out = {"data": shape, "positions": shape,
                       "page_table": (b, self.max_pages)}
                if width is None:  # the decode graph picks and feeds on
                    out.update(source=shape, prev_ids=shape)
                    if pool.num_slots:
                        out.update(state_slot=shape)
                return out

            # outputs: the logits, then the planes in the pool's order
            # (then, of the decode graph, the picked ids)
            names = pool.plane_names()
            planes = dict(zip(names, pool.planes()))
            carried = {name: 1 + i for i, name in enumerate(names)}
            shapes = feeds(self.max_lanes)
            shapes.update({k: v.shape for k, v in planes.items()})
            base = Predictor(symbol, dict(params, **planes), shapes,
                             ctx=ctx, dtype=_CARRIER)
            rig = {self.max_lanes: base}
            for b in self.lane_buckets[:-1]:
                rig[b] = base.reshape(feeds(b))
            for b, pred in rig.items():
                pred._exec._cache_kind = kind
                pred._exec._program_name = name % b
                pred._exec.set_carried(carried)
            return rig

        self._prefill = prefill_rig(family, self._params, "gen-prefill",
                                    "prefill_L%d")
        self._decode = lane_rig(family, self._params, self.pool, "gen-step",
                                "decode_b%d")
        self._ssm_step = _state_step(
            self._decode[self.max_lanes]._symbol, self.pool)
        longest = self._prefill[self.prefill_len_buckets[-1]]
        self._ssm_scan = _state_scan(
            longest._preds[longest.max_batch_size]._symbol, self._params)
        self._sequence_heads = _sequence_heads(
            longest._preds[longest.max_batch_size]._symbol, family)
        # the lane program's outputs after the picked ids, and the routed
        # experts' cumulative load (expert layers, experts) where it has one
        self._lane_extras = tuple(getattr(family, "lane_extras", ()))
        self._moe_experts = _expert_products(
            self._decode[self.max_lanes]._symbol, self._params)
        self._expert_load = None
        self._expert_steps = self._experts_hit_total = 0
        # bytes a token holds over the family's latent planes (0: none)
        self._latent_token_bytes = int(
            getattr(family, "latent_token_bytes", lambda: 0)())
        self._latent_rows = _latent_rows(
            self._decode[self.max_lanes]._symbol, self._params, self.pool)
        # bytes of a lane's rings over the family's sliding-window layers
        # (0: none): slots that a step reads whole and writes one row of
        self._ring_bytes = int(getattr(family, "ring_bytes", lambda: 0)())

        # -- speculative rig: draft pool + prefill + decode, target verify
        self._draft_pool: Optional[PagedKVPool] = None
        self._draft_prefill: Dict[int, BucketedPredictor] = {}
        self._draft_decode: Dict[int, "Predictor"] = {}
        self._verify: Dict[int, "Predictor"] = {}
        if self._draft is not None:
            small = generator_family(
                None, self.vocab_size, self._draft["num_layers"],
                self._draft["num_heads"], self._draft["hidden"], self._dtype)
            self._draft_pool = PagedKVPool(self.num_pages, self.page_size,
                                           planes=small.planes(), ctx=ctx)
            self._draft_prefill = prefill_rig(
                small, self._draft_params, "gen-draft-prefill",
                "draft_prefill_L%d")
            self._draft_decode = lane_rig(
                small, self._draft_params, self._draft_pool,
                "gen-draft-step", "draft_decode_b%d")
            # verification is teacher forcing too — the draft's K
            # proposals are known before the call — so the verify rig
            # uses the same windowed single-pass graph as catch-up
            # rather than chaining K+1 literal decode blocks (whose
            # dispatch cost eats the speculation win on small models)
            self._verify = lane_rig(
                family, self._params, self.pool, "gen-verify", "verify_b%d",
                width=self._verify_width)

        # -- prefix-cache catch-up rig: a windowed teacher-forcing
        # executable that re-walks the KNOWN suffix of a partial prefix
        # hit (or a re-admitted preemptee) ``catchup_width`` slots per
        # forward instead of one per decode iteration, so cached
        # admissions reach their first token in one decode step no
        # matter where the index's page-granular match stopped
        self._catchup: Dict[int, "Predictor"] = {}
        self._catchup_width = 0
        if self.prefix_cache_pages:
            # wide enough to swallow a typical page-rounding suffix in
            # one forward — every extra round pays the executable's fixed
            # dispatch cost; the windowed pass itself is
            # compute-proportional, so a wider window costs only the pad
            # slots it doesn't use
            self._catchup_width = max(2, min(32, self.max_seq_len - 1))
            self._catchup = lane_rig(
                family, self._params, self.pool, "gen-catchup",
                "catchup_b%d", width=self._catchup_width)

        # (batch, L, vocab) logits -> the greedy id of each prompt's last
        # row, on the device: one program a shape (``_first_ids``)
        self._prefill_rows: Dict[tuple, object] = {}

        # recompile-detector bookkeeping: lane buckets warmup compiled,
        # post-warmup steps that hit a novel (never-warmed) bucket
        self.warmed_lane_buckets = set()
        self._warned_lane_buckets = set()
        self.decode_cold_runs = 0

        self._cv = threading.Condition()
        self._pending: deque = deque()  # _Seq, FIFO (preempted go front)
        self._active: List[_Seq] = []
        # the plain step dispatched and not yet read (engine thread only)
        self._inflight: Optional[_Flight] = None
        self._sid = 0
        self._closed = False
        self._drain = True
        self._loop_thread = threading.Thread(
            target=self._loop, name="mxtpu-gen-engine", daemon=True)
        self._started = False
        if warmup:
            self.warmup()
        if start:
            self.start()

    # -- construction helpers ---------------------------------------------
    def _first_ids(self, logits, rows):
        """The greedy id of row ``rows[b]`` of each prompt's logits, picked
        on the device."""
        program = self._prefill_rows.get(logits.shape)
        if program is None:
            import jax
            import jax.numpy as jnp

            def prefill_rows(logits, rows):
                return jnp.argmax(jnp.take_along_axis(
                    logits, rows[:, None, None], axis=1)[:, 0], axis=-1)

            program = self._prefill_rows[logits.shape] = _first_call(
                jax.jit(prefill_rows), "gen-prefill", functools.partial(
                    self._prefill_rows.__setitem__, logits.shape))
        return program(logits, rows)

    def spec(self) -> Dict:
        """Model/engine geometry needed to rebuild this engine against a
        new checkpoint (hot-swap, AOT warmup manifests, shadow replicas).
        The draft block carries the RESOLVED speculative K."""
        out = {
            **self.family.engine_spec(),
            "max_seq_len": self.max_seq_len,
            "lane_buckets": list(self.lane_buckets),
            "page_size": self.page_size, "num_pages": self.num_pages,
            "prefill_len_buckets": list(self.prefill_len_buckets),
            "prefill_batch_buckets": list(self.prefill_batch_buckets),
            "eos_id": self.eos_id, "max_pending": self.max_pending,
            "prefix_cache_pages": self.prefix_cache_pages,
        }
        if self._draft is not None:
            out["draft"] = dict(self._draft)
        return out

    @classmethod
    def from_checkpoint(cls, prefix, epoch, **spec):
        """Build from ``save_checkpoint`` files; ``spec`` as for the
        constructor (see :meth:`spec`)."""
        return cls("%s-%04d.params" % (prefix, int(epoch)), **spec)

    def warmup(self):
        """Pre-compile every prefill (length x batch) bucket with the
        pool's scatter of its shape, and every decode/draft/verify lane
        bucket, priming through the compile cache when it is enabled —
        post-warmup steady state performs ZERO XLA compiles, and an
        attached AOT bundle makes the executors' warmup
        deserialize-only."""
        for bp, pool in self._prefill_rigs():
            bp.warmup()
            # what a prefill does with the outputs, once per shape: the
            # scatter into the planes (scratch page 0 here), and for the
            # target the pick of each prompt's first token
            for b, pred in bp._preds.items():
                outs = pred.get_outputs()
                pool.write_slots([o._data for o in outs[1:]],
                                 np.zeros((b,) + bp.item_shapes["data"],
                                          np.int32))
                if pool is self.pool:
                    np.asarray(self._first_ids(
                        outs[0]._data, np.zeros((b,), np.int32)))
        if self.prefix_cache_pages:
            self.pool.copy_page(0, 0)  # the copy-on-write split's program
        for b in self.lane_buckets:
            rigs = [(self._decode[b], (b,))]
            if self._draft is not None:
                rigs.append((self._draft_decode[b], (b,)))
                rigs.append((self._verify[b], (b, self._verify_width)))
            if self._catchup:
                rigs.append((self._catchup[b], (b, self._catchup_width)))
            for pred, dshape in rigs:
                # all-zero feeds: every lane writes scratch page 0 of the
                # pool's own planes, which the rig binds
                self._run_lanes(pred, np.zeros(dshape, _CARRIER),
                                np.zeros(dshape, _CARRIER),
                                np.zeros((b, self.max_pages), _CARRIER))
            self.warmed_lane_buckets.add(b)
        return self

    def _prefill_rigs(self):
        """(prefill family, the pool its K/V goes to), target then draft."""
        return [(bp, self.pool) for bp in self._prefill.values()] + \
            [(bp, self._draft_pool) for bp in self._draft_prefill.values()]

    def compiled_entries(self):
        """Primed compile-cache wrappers across prefill, decode, draft,
        verify and catch-up executors (kinds ``gen-prefill`` /
        ``gen-step`` / ``gen-draft-prefill`` / ``gen-draft-step`` /
        ``gen-verify`` / ``gen-catchup``) —
        the input to ``checkpoint.save_aot_bundle`` so an autoscaled
        replica serves its first generate request with zero cold
        compiles."""
        from ..compile_cache import CachedFunction

        out = []
        for bp in list(self._prefill.values()) + \
                list(self._draft_prefill.values()):
            out.extend(bp.compiled_entries())
        preds = (list(self._decode.values())
                 + list(self._draft_decode.values())
                 + list(self._verify.values())
                 + list(self._catchup.values()))
        for pred in preds:
            for fn in pred._exec._jit_cache.values():
                if isinstance(fn, CachedFunction):
                    out.append(fn)
        return out

    def cold_decode_runs(self) -> int:
        """Post-warmup decode steps that hit a never-warmed lane bucket
        plus cold prefill flushes — 0 is the "steady state never
        recompiles" acceptance check."""
        return (self.decode_cold_runs
                + sum(bp.cold_runs for bp in self._prefill.values())
                + sum(bp.cold_runs
                      for bp in self._draft_prefill.values()))

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if not self._started:
            self._started = True
            self._loop_thread.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = 30.0):
        """Stop the engine.  With ``drain`` (default) queued and active
        sequences finish first (bounded by ``timeout`` seconds), without
        it they fail fast with :class:`ServerClosedError`."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._drain = drain
            if not drain:
                self._fail_all_locked(ServerClosedError(
                    "engine stopped before completion"))
            self._cv.notify_all()
        if self._started:
            self._loop_thread.join(timeout)
        with self._cv:
            # drain deadline expired with work outstanding (or fail-fast
            # stop racing the loop): cancel whatever is left
            self._fail_all_locked(ServerClosedError("engine stopped"))

    def handoff(self) -> int:
        """Preempt every queued and active stream WITHOUT stopping the
        engine: each fails with :class:`ServerClosedError`, which a
        router-level consumer treats as a replica failure and re-submits
        (prompt + emitted tokens) on a surviving replica — greedy decode
        makes the resumed transcript bit-identical.  The graceful
        page-out handoff: call this before the owning server releases
        its device memory.  Returns the number of streams handed off."""
        with self._cv:
            n = len(self._pending) + len(self._active)
            self._fail_all_locked(ServerClosedError(
                "replica preempted: stream handed off"))
            self._cv.notify_all()
        if n:
            _telemetry.log_event("gen_handoff", streams=n)
        return n

    def _fail_all_locked(self, exc):
        n = 0
        for seq in list(self._pending) + list(self._active):
            self.pool.free(seq.sid)
            if self._draft_pool is not None:
                self._draft_pool.free(seq.sid)
            seq.stream._finish(exc)
            n += 1
        self._pending.clear()
        del self._active[:]
        if n:
            self.metrics.failed.inc(n)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop(drain=True)

    # -- request path ------------------------------------------------------
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> GenStream:
        """Queue one prompt for generation; returns its
        :class:`GenStream`.  Raises :class:`QueueFullError` when the
        pending queue is at capacity (HTTP 429 — retry with backoff) and
        :class:`MXNetError` for prompts that can never fit."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("empty prompt")
        max_new = int(self.default_max_new if max_new_tokens is None
                      else max_new_tokens)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        total = len(prompt) + max_new
        if total > self.max_seq_len:
            raise MXNetError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_seq_len %d"
                % (len(prompt), max_new, self.max_seq_len))
        if self.pool.pages_for(total) > self.pool.capacity:
            raise MXNetError(
                "request needs %d KV pages but the pool only has %d — it "
                "can never be admitted" %
                (self.pool.pages_for(total), self.pool.capacity))
        stream = GenStream(prompt, max_new)
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        with self._cv:
            if self._closed:
                raise ServerClosedError("engine is stopped")
            if len(self._pending) >= self.max_pending:
                self.metrics.rejected.inc()
                raise QueueFullError(
                    "generation queue full (%d pending); retry with "
                    "backoff" % len(self._pending))
            stream.sid = self._sid
            self._pending.append(_Seq(self._sid, stream, deadline,
                                      self.eos_id))
            self._sid += 1
            self.metrics.g_pending.set(len(self._pending))
            self._cv.notify_all()
        return stream

    def generate(self, prompt, max_new_tokens=None, deadline_ms=None,
                 timeout: Optional[float] = 300.0) -> List[int]:
        """Blocking convenience wrapper: the full generated token list."""
        return self.submit(prompt, max_new_tokens,
                           deadline_ms=deadline_ms).result(timeout)

    def pending_depth(self) -> int:
        with self._cv:
            return len(self._pending)

    def active_lanes(self) -> int:
        with self._cv:
            return len(self._active)

    def devices(self) -> Dict[str, List[str]]:
        """Where this engine's arrays live, as ``str(jax.Device)`` lists:
        ``weights`` (the shared parameter copy), ``pool`` (the K/V
        planes) and ``prefill`` / ``decode`` (output buffers of every
        executable that has run — all of them after :meth:`warmup`)."""
        def of(arrays):
            return sorted({str(d) for a in arrays
                           for d in a._data.devices()})

        prefill = [o for bp in self._prefill.values()
                   for p in bp._preds.values() for o in p.get_outputs()]
        decode = [o for p in self._decode.values() for o in p.get_outputs()]
        return {"weights": of(self._params.values()),
                "pool": self.pool.devices(),
                "prefill": of(prefill), "decode": of(decode)}

    def _paged_formulation(self):
        """Which formulation the lane program's ``_contrib_PagedAttention``
        runs over this engine's K/V planes, where they live (a family that
        pages no K/V, the latent one, says what its own op runs under
        ``latent_attention``)."""
        if not self.pool.k_pools:
            return "xla"
        plane = self.pool.k_pools[0]
        return decode_formulation(
            self._device.platform, self.num_heads, self.head_dim, plane.dtype,
            kv_heads=self.family.kv_heads, rows=len(plane.shape) == 3,
            page_size=self.pool.page_size)

    def snapshot(self) -> dict:
        # what this process's start was spent on (spans by name, the
        # compile ledger by program): read before the lock is taken
        startup = _telemetry.startup_report()
        with self._cv:
            snap = {"pending": len(self._pending),
                    "active": len(self._active),
                    "tokens_total": self.metrics.tokens.value,
                    "steps": self.metrics.steps.value,
                    "steps_overlapped": self.metrics.steps_overlapped.value,
                    "tokens_dropped": self.metrics.tokens_dropped.value,
                    "cold_decode_runs": self.cold_decode_runs(),
                    "prefix_cache_pages": self.prefix_cache_pages,
                    # whether the step's program updates the planes in
                    # place (None before it is built; False where the
                    # executable may be serialized: executor.py)
                    "step_donated": next(
                        (p._exec.carry_donated
                         for p in self._decode.values()
                         if p._exec.carry_donated is not None), None),
                    # which formulation the lane program's attention runs
                    # where this engine's planes live (ops/paged.py)
                    "paged_attention": self._paged_formulation(),
                    "kv": self.pool.snapshot(), "startup": startup}
            if self.pool.num_slots:
                snap["state_slots"] = snap["kv"]["state_slots"]
            if self._ssm_step:
                # likewise for the lane program's state step (ops/ssm.py)
                snap["ssm_step"] = step_formulation(
                    self._device.platform, *self._ssm_step)
            if self._ssm_scan:
                # and for the prefill programs' scan, at the longest bucket
                snap["ssm_scan"] = scan_formulation(
                    self._device.platform, self.prefill_len_buckets[-1],
                    is_train=False, **self._ssm_scan)
            if self._sequence_heads:
                # and for their grouped-query attention over the sequence
                # (ops/paged.py), at the longest bucket too
                snap["sequence_attention"] = sequence_formulation(
                    self._device.platform, self.prefill_len_buckets[-1],
                    self._sequence_heads, self.family.kv_heads,
                    self.head_dim, self.family.dtype, is_train=False)
            if self._latent_rows:
                # likewise for the latent layers' two ops (ops/paged.py)
                snap["latent_attention"] = {
                    "prefill": LATENT_PREFILL,
                    "decode": latent_formulation(
                        self._device.platform, self.num_heads,
                        *self._latent_rows, page_size=self.pool.page_size)}
            if self._ring_bytes:
                # and for the sliding-window layers' lane form
                snap["window_attention"] = WINDOW_STEP
            if self._lane_extras:
                # likewise for the routed experts' grouped products
                # (ops/moe.py), and what the lanes picked so far
                snap["moe_experts"] = experts_formulation(
                    self._device.platform, *self._moe_experts)
                snap["experts"] = self._experts_snapshot()
            if self._draft is not None:
                snap["draft"] = {
                    "k": self._draft["k"],
                    "proposed": self.metrics.draft_proposed.value,
                    "accepted": self.metrics.draft_accepted.value,
                    "accept_rate_ewma": self._accept_ewma,
                    "fallbacks": self.metrics.spec_fallbacks.value,
                    "kv": self._draft_pool.snapshot(),
                }
            return snap

    def _experts_snapshot(self):
        """The routed experts' load since the start (``_cv`` held): by layer
        and expert, the mean count of experts hit a step and layer, and the
        worst layer's busiest expert over its mean one."""
        load, steps = self._expert_load, self._expert_steps
        if load is None:
            return {"steps": 0, "load": [], "hit_per_step_layer": None,
                    "max_over_mean": None}
        mean = np.maximum(load.mean(axis=1), 1e-9)
        return {"steps": steps, "load": load.tolist(),
                "hit_per_step_layer": self._experts_hit_total
                / float(steps * load.shape[0]),
                "max_over_mean": float((load.max(axis=1) / mean).max())}

    # -- engine loop -------------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while not self._pending and not self._active \
                        and self._inflight is None and not self._closed:
                    self._cv.wait(0.05)
                if self._closed and not self._active and \
                        self._inflight is None and \
                        (not self._pending or not self._drain):
                    for seq in self._pending:
                        seq.stream._finish(ServerClosedError(
                            "engine stopped before execution"))
                    self._pending.clear()
                    return
            try:
                self._admit()
                if self._active or self._inflight is not None:
                    self._decode_step()
            except BaseException as exc:  # fault-injected or real: contain
                logging.warning("generation engine step failed: %r", exc)
                self._inflight = None  # its lanes fail with the rest
                with self._cv:
                    self._fail_all_locked(exc)
                _telemetry.log_event("gen_engine_error", error=repr(exc))

    def _prefill_bucket_for(self, n: int) -> int:
        for L in self.prefill_len_buckets:
            if L >= n:
                return L
        raise MXNetError("prompt of %d exceeds largest prefill bucket %d"
                         % (n, self.prefill_len_buckets[-1]))

    def _admit(self):
        """Move pending sequences into free decode lanes: allocate KV
        pages (resolving the prompt against the prefix index), run
        bucketed prefill for the cache misses, stream each prefilled
        sequence's first token.  Cached sequences go straight to decode
        lanes — zero prefill steps."""
        batch: List[_Seq] = []
        now = time.monotonic()
        avail = self.pool.reclaimable_pages()
        d_avail = (self._draft_pool.free_pages()
                   if self._draft_pool is not None else None)
        slots = self.pool.free_slots()  # None: the family carries none
        with self._cv:
            while self._pending and (slots is None or len(batch) < slots) \
                    and len(self._active) + len(batch) < self.max_lanes:
                seq = self._pending[0]
                if seq.deadline is not None and now > seq.deadline:
                    self._pending.popleft()
                    self.metrics.expired.inc()
                    seq.stream._finish(DeadlineExceededError(
                        "request waited past its TTFT deadline"))
                    continue
                need = self.pool.pages_for(len(seq.tokens))
                if need > avail or (d_avail is not None and need > d_avail):
                    break  # wait for active lanes to retire/free pages
                avail -= need
                if d_avail is not None:
                    d_avail -= need
                self._pending.popleft()
                batch.append(seq)
            self.metrics.g_pending.set(len(self._pending))
        if not batch:
            return
        with _span("gen:admit", "gen", {"n": len(batch)}):
            faults.fire("generation.engine.admit")
            # group by prompt-length bucket, chunk to the prefill batch cap
            by_bucket: Dict[int, List[_Seq]] = {}
            for seq in batch:
                by_bucket.setdefault(
                    self._prefill_bucket_for(len(seq.tokens)),
                    []).append(seq)
            for L, seqs in sorted(by_bucket.items()):
                bp = self._prefill[L]
                cap = bp.max_batch_size
                for ofs in range(0, len(seqs), cap):
                    self._prefill_group(L, seqs[ofs:ofs + cap])

    def _prefill_group(self, L: int, seqs: List[_Seq]):
        admitted: List[_Seq] = []
        for seq in seqs:
            try:
                _, cached = self.pool.alloc_prefix(
                    seq.sid, len(seq.tokens),
                    tokens=(seq.tokens if self.prefix_cache_pages
                            else None))
            except KVPoolExhaustedError:
                # admission raced a concurrent consumer: wait a round
                with self._cv:
                    self._pending.appendleft(seq)
                continue
            if self._draft_pool is not None:
                try:
                    self._draft_pool.alloc(seq.sid, len(seq.tokens))
                except KVPoolExhaustedError:
                    self.pool.free(seq.sid)
                    with self._cv:
                        self._pending.appendleft(seq)
                    continue
            seq.next_pos = cached  # 0 on a miss: full prefill below
            if cached:
                seq.stream.cached_prefix_tokens += cached
                self.metrics.cached_admissions.inc()
            admitted.append(seq)
        if not admitted:
            return
        misses = [s for s in admitted if s.next_pos == 0]
        args = {"bucket": L, "n": len(admitted),
                "tokens": sum(len(s.tokens) for s in misses)}
        if self.pool.num_slots:
            args["state_slot"] = "|".join(
                str(self.pool.state_slot(s.sid)) for s in admitted)
        if self._ssm_scan:
            # the chunks the bucket holds a prompt, and those with a token
            # in them: what the scan walks (ops/ssm.py)
            chunk = self._ssm_scan["chunk"]
            args["scan_chunks"] = len(misses) * -(-L // chunk)
            args["scan_chunks_live"] = sum(-(-len(s.tokens) // chunk)
                                           for s in misses)
        if "expert_load" in self._lane_extras:
            # every prompt token routes in every expert layer
            args["expert_pairs"] = self.family.expert_pairs(args["tokens"])
            if misses:
                args.update(self._experts_moved(
                    self._prefill[L].bucket_for(len(misses)) * L))
        with _span("gen:prefill", "gen", args):
            start = time.monotonic()
            for seq in admitted:
                wait_ms = max(0.0, (start - seq.stream._t0) * 1e3)
                with _span("gen:queued", "gen",
                           {"sid": seq.sid, "wait_ms": round(wait_ms, 3)}):
                    pass
            self._prefill_admitted(L, admitted, misses)

    def _prefill_admitted(self, L: int, admitted: List[_Seq],
                          misses: List[_Seq]):
        """Run the prefill executables for one admitted group and move it
        into the decode lanes."""
        # the draft holds no prefix cache: prefill EVERY admitted
        # sequence through the draft model so proposals can start from
        # the first decode iteration
        def prefill(bp, pool, seqs):
            """One prefill forward for ``seqs``; its K/V (and final
            states) go from the program's outputs into the pool's planes
            on the device."""
            items = []
            for seq in seqs:
                buf = np.zeros((L,), _CARRIER)
                buf[:len(seq.tokens)] = seq.tokens
                items.append({"data": buf})
                if "length" in bp.item_shapes:  # the graph takes it
                    items[-1]["length"] = len(seq.tokens)
            _, outs = bp.run_batch(items)
            pool.write_prefill([s.sid for s in seqs],
                               [o._data for o in outs[1:]],
                               [len(s.tokens) for s in seqs])
            return outs[0]._data  # logits (batch, L, vocab)

        if self._draft is not None:
            prefill(self._draft_prefill[L], self._draft_pool, admitted)
            for seq in admitted:
                seq.draft_pos = len(seq.tokens)
        if misses:
            logits = prefill(self._prefill[L], self.pool, misses)
            rows = np.zeros((logits.shape[0],), np.int32)
            rows[:len(misses)] = [len(s.tokens) - 1 for s in misses]
            # the one read of a prefill: each prompt's first token
            first = np.asarray(self._first_ids(logits, rows))
            for i, seq in enumerate(misses):
                n = len(seq.tokens)
                seq.stream.prefill_tokens += n
                seq.next_pos = n
                if self.prefix_cache_pages:
                    self.pool.register_prefix(seq.sid, seq.tokens[:n])
                self._emit(seq, int(first[i]))
        if self.prefix_cache_pages:
            self._catchup_group([s for s in admitted if s not in misses])
        for seq in admitted:
            seq.admitted_at = time.monotonic()
        with self._cv:
            self._active.extend(s for s in admitted
                                if not s.stream.done)
            self.metrics.admitted.inc(len(admitted))
            self.metrics.g_active.set(len(self._active))

    def _experts_moved(self, rows):
        """What an expert layer of a program over ``rows`` rows moves at the
        width of a row, as span arguments: ``experts_path`` (``ops/moe.py``
        ``experts_path``: ``"held"`` or ``"all"``) and ``expert_pairs_moved``,
        the share of the layer's (row, pick) pairs that path moves, by the
        static share of the experts held here (1.0 for ``"all"``)."""
        held = self.family.experts_held
        path = experts_path(
            experts_formulation(self._device.platform, *self._moe_experts),
            held, self.family.num_experts,
            rows * self.family.experts_per_token)
        share = held / self.family.num_experts if path == "held" else 1.0
        return {"experts_path": path, "expert_pairs_moved": round(share, 4)}

    def _catchup_group(self, seqs: List[_Seq]):
        """Batch-walk the KNOWN suffix of prefix hits through the
        windowed catch-up executable — ``catchup_width`` positions
        per forward instead of one per decode iteration — feeding
        THROUGH the final prompt position and emitting the first
        generated token from the last slot's logits.  A cached
        admission therefore reaches its first token inside admission,
        in ``ceil(suffix / catchup_width)`` forwards, with no separate
        decode step: TTFT stays one engine iteration regardless of how
        far short of the prompt the index's page-granular match fell."""
        pending = [s for s in seqs
                   if 0 < s.next_pos < len(s.tokens)]
        if not pending or not self._catchup:
            return
        W = self._catchup_width
        while pending:
            b = self._lane_bucket_for(len(pending))
            self._note_lane_bucket(b)
            pred = self._catchup[b]
            data = np.zeros((b, W), _CARRIER)
            # pads park in the scratch page's last slot (zero table row)
            positions = np.full((b, W), self.max_seq_len - 1, dtype=_CARRIER)
            table = np.zeros((b, self.max_pages), _CARRIER)
            spans = []
            for i, seq in enumerate(pending):
                # the cursor's page can still be prefix-indexed/shared
                self.pool.ensure_writable(seq.sid, seq.next_pos)
                span = min(W, len(seq.tokens) - seq.next_pos)
                data[i, :span] = seq.tokens[seq.next_pos:
                                            seq.next_pos + span]
                positions[i, :span] = np.arange(seq.next_pos,
                                                seq.next_pos + span)
                table[i] = self.pool.page_table_row(seq.sid,
                                                    self.max_pages)
                spans.append(span)
            logits = self._run_lanes(pred, data, positions, table)
            logits = logits.reshape(b, W, -1)  # (lanes, width, vocab)
            nxt = []
            for i, (seq, span) in enumerate(zip(pending, spans)):
                seq.iters += 1
                seq.next_pos += span
                self.pool.register_prefix(seq.sid,
                                          seq.tokens[:seq.next_pos])
                if seq.next_pos >= len(seq.tokens):
                    # crossed into generation: the last fed slot's
                    # logits seed the stream's first token
                    self._emit(seq, int(np.argmax(logits[i, span - 1])))
                else:
                    nxt.append(seq)
            pending = nxt

    def _emit(self, seq: _Seq, tok: int):
        """Stream one generated token; retires the sequence when it hit
        its budget or EOS.  Returns True when the sequence retired."""
        first = not seq.stream.tokens
        gap = seq.stream._emit(tok)
        if first:
            seq.stream.ttft_iters = seq.iters
        seq.tokens.append(tok)
        seq.gen_count += 1
        self.metrics.tokens.inc()
        (self.metrics.ttft if first else self.metrics.itl).observe(gap)
        if seq.gen_count >= seq.max_new or \
                (seq.eos_id is not None and tok == seq.eos_id):
            self._retire(seq)
            return True
        return False

    def _retire(self, seq: _Seq):
        faults.fire("generation.engine.retire")
        if self.prefix_cache_pages:
            # publish the finished transcript's complete pages before
            # releasing them: a refcount-0 indexed page is retained as
            # cache, so the next request sharing this prefix hits
            self.pool.register_prefix(seq.sid, seq.tokens[:seq.next_pos])
        self.pool.free(seq.sid)
        if self._draft_pool is not None:
            self._draft_pool.free(seq.sid)
        seq.stream._finish(None)
        self.metrics.retired.inc()

    def _preempt_one(self, exclude: Optional[_Seq] = None) -> bool:
        """Free the youngest active lane's pages and push the sequence
        back to the FRONT of the pending queue for re-admission of
        prompt + generated-so-far (greedy decode is deterministic, so
        its stream continues without a hiccup).  Its complete pages are
        published to the prefix index first, so with caching enabled
        the re-admission is a prefix HIT instead of a full re-prefill."""
        with self._cv:
            victims = [s for s in self._active if s is not exclude]
            if not victims:
                victims = [s for s in self._active]
            if not victims:
                return False
            victim = max(victims, key=lambda s: s.admitted_at)
            self._active.remove(victim)
            # a recurrent family's state is rebuilt by a prefill over the
            # whole transcript, or not at all
            lost = bool(self.pool.num_slots) and \
                len(victim.tokens) > self.prefill_len_buckets[-1]
            if not lost:
                self._pending.appendleft(victim)
            self.metrics.g_active.set(len(self._active))
            self.metrics.g_pending.set(len(self._pending))
        if lost:
            self.pool.free(victim.sid)
            victim.stream._finish(StateNotRebuildableError(
                "sequence %d was preempted after %d tokens: its recurrent "
                "state can only be rebuilt by a prefill over the whole "
                "transcript, and the largest prefill bucket is %d"
                % (victim.sid, len(victim.tokens),
                   self.prefill_len_buckets[-1])))
            self.metrics.preempted.inc()
            self.metrics.failed.inc()
            return True
        if self.prefix_cache_pages:
            self.pool.register_prefix(victim.sid,
                                      victim.tokens[:victim.next_pos])
        self.pool.free(victim.sid)
        if self._draft_pool is not None:
            self._draft_pool.free(victim.sid)
        victim.next_pos = 0
        victim.draft_pos = 0
        self.metrics.preempted.inc()
        _telemetry.log_event("gen_preempt", sid=victim.sid,
                             tokens=len(victim.tokens))
        return True

    def _lane_bucket_for(self, n: int) -> int:
        for b in self.lane_buckets:
            if b >= n:
                return b
        raise MXNetError("%d active lanes exceed largest bucket %d"
                         % (n, self.lane_buckets[-1]))

    def _note_lane_bucket(self, b: int):
        if b in self.warmed_lane_buckets:
            return
        self.decode_cold_runs += 1
        self.metrics.cold_steps.inc()
        self.warmed_lane_buckets.add(b)
        if b not in self._warned_lane_buckets:
            self._warned_lane_buckets.add(b)
            logging.warning(
                "generation: decode step hit never-warmed lane bucket "
                "%d post-warmup (fresh XLA compile on the serving "
                "path) — add it to lane_buckets/warmup", b)
            _telemetry.log_event("gen_decode_cold_bucket", lanes=b)

    def _decode_step(self):
        """One continuous-batching iteration: grow every lane's KV
        allocation for the positions about to be written (pool
        exhaustion preempts the youngest other lane), copy-on-write any
        shared page under the feed cursor, then advance every lane —
        one token via the decode executable, or up to K+1 via the
        draft/verify speculative pass."""
        faults.fire("generation.engine.step")
        # a lane whose budget ends with the token the step in flight picks
        # takes no part in this one: the host knows that a step early
        due = [s for s in self._active if s.next_pos + 1 < s.limit]
        if not due:
            # nothing to dispatch: what is left awaits the step in flight
            with _span("gen:drain", "gen") as span:
                flight, self._inflight = self._inflight, None
                self._read_step(flight, span)
            return
        with _span("gen:step", "gen") as span:
            self._grow_lanes(due)
            lanes = [s for s in due if s in self._active]
            if not lanes:
                return
            if self._draft is not None:
                self._spec_step(lanes, span)
            else:
                self._plain_step(lanes, span)

    def _grow_lanes(self, lanes: List[_Seq]):
        """Extend every lane's pages to the positions this iteration
        writes, preempting the youngest other lane when the pool is out."""
        width = self._verify_width
        with _span("gen:grow", "gen") as span:
            preempted = 0
            for seq in lanes:
                # an earlier lane's extend may have preempted this one
                while seq in self._active:
                    try:
                        tgt = min(seq.next_pos + width, seq.limit,
                                  self.max_seq_len)
                        self.pool.extend(seq.sid, tgt)
                        if self.prefix_cache_pages:
                            # the page under the cursor may be shared
                            # (cached admission) or still prefix-indexed:
                            # split it before this iteration writes K/V
                            self.pool.ensure_writable(seq.sid, seq.next_pos)
                        if self._draft_pool is not None:
                            self._draft_pool.extend(seq.sid, tgt)
                        break
                    except KVPoolExhaustedError:
                        if not self._preempt_one(exclude=seq):
                            raise
                        preempted += 1
            span.set(preempted=preempted)

    def _describe_step(self, span, lanes: List[_Seq], b: int, **more):
        """What a ``gen:step`` span says of the step it dispatches."""
        span.set(lanes=len(lanes), bucket=b,
                 sids="|".join(str(s.sid) for s in lanes),
                 # the pages that hold the lanes' tokens up to this step's:
                 # what paged attention walks (times page_size: the live
                 # tokens)
                 pages=sum(s.next_pos // self.page_size + 1 for s in lanes),
                 **more)

    def _dispatch_lanes(self, pred, data, positions, table, source=None,
                        slots=None):
        """Upload one lane-bucket executable's feeds and dispatch it;
        returns its outputs, unread.  Ids, positions and tables go up; the
        planes the executable binds are the pool's own and stay on the
        device (``Executor.set_carried``).  The decode graph also takes
        ``source`` (``None``: every lane feeds ``data``) and hands its
        picked ids on to its own next call; a family with state planes
        takes ``state_slot`` (``None``: every lane on scratch slot 0)."""
        import jax

        args = pred._exec.arg_dict
        feeds = {"data": data, "positions": positions, "page_table": table}
        picks = "source" in args  # the decode graph
        if picks:
            feeds["source"] = (np.full(data.shape, -1, _CARRIER)
                               if source is None else source)
        if "state_slot" in args:
            feeds["state_slot"] = (np.zeros(data.shape, _CARRIER)
                                   if slots is None else slots)
        with _span("gen:pool_h2d", "gen",
                   {"bytes": sum(v.nbytes for v in feeds.values())}):
            put = jax.device_put(tuple(feeds.values()), self._device)
            for name, fed in zip(feeds, put):
                args[name]._set(fed)
        with _span("gen:forward", "gen"):
            outs = pred._exec.forward(is_train=False)
        if picks:
            # rebound, not carried: a carried argument is donated, and the
            # engine reads these ids after the next call's dispatch
            args["prev_ids"]._set(self._picked(outs)._data)
        return outs

    def _picked(self, outs):
        """The decode graph's ``next_ids`` among its outputs: the last but
        for the family's ``lane_extras``."""
        return outs[-1 - len(self._lane_extras)]

    def _run_lanes(self, pred, data, positions, table):
        """Run one lane-bucket executable and return its logits."""
        outs = self._dispatch_lanes(pred, data, positions, table)
        # the read blocks until the device has run the step: this span
        # holds the device's own work as well as the logits' way down
        with _span("gen:pool_d2h", "gen") as span:
            logits = outs[0].asnumpy()
            span.set(bytes=logits.nbytes)
        return logits

    def _plain_step(self, lanes: List[_Seq], span):
        """Advance every lane one position through the decode executable,
        one step in flight: this iteration's step is dispatched BEFORE the
        ids of the step ahead of it are read, because the program picks the
        token and feeds it on (``prev_ids`` / ``source``) and the host
        knows positions, page tables and budgets without it.  What it does
        not know is an EOS: such a lane rides one step more, and that
        token is dropped.  A page is never handed to another sequence by a
        program dispatched before the last step that names it: the device
        runs programs in dispatch order and ``pool.free`` of a lane comes
        after the dispatch of the last step that fed it."""
        prev, self._inflight = self._inflight, None
        b = self._lane_bucket_for(len(lanes))
        early = prev is not None and prev.pred is not self._decode[b]
        if early:
            # the ids in flight are another lane count's program's: they
            # come down first and every lane is fed from the host
            self._read_step(prev, span)
            prev = None
            lanes = [s for s in lanes if s in self._active]
            if not lanes:
                return
            b = self._lane_bucket_for(len(lanes))
        self._inflight = self._dispatch_step(lanes, b, prev, span)
        if not early:
            self._read_step(prev, span)

    def _dispatch_step(self, lanes: List[_Seq], b: int,
                       prev: Optional[_Flight], span) -> _Flight:
        """Feed ``tokens[next_pos]`` at ``next_pos`` for every lane and
        dispatch the step.  A lane whose token the host does not hold yet
        (``next_pos == len(tokens)``: ``prev`` is computing it) takes it on
        the device from its lane of ``prev``."""
        self._note_lane_bucket(b)
        came = {seq.sid: j for j, (seq, _) in enumerate(prev.lanes)} \
            if prev is not None else {}
        pool, more = self.pool, {}
        slots = np.zeros((b,), _CARRIER) if pool.num_slots else None
        with _span("gen:feed", "gen"):
            data = np.zeros((b,), _CARRIER)
            positions = np.zeros((b,), _CARRIER)
            source = np.full((b,), -1, _CARRIER)
            table = np.zeros((b, self.max_pages), _CARRIER)
            for i, seq in enumerate(lanes):
                if seq.next_pos < len(seq.tokens):
                    data[i] = seq.tokens[seq.next_pos]
                else:
                    source[i] = came[seq.sid]
                positions[i] = seq.next_pos  # slot the new K/V lands in
                table[i] = pool.page_table_row(seq.sid, self.max_pages)
                if slots is not None:
                    slots[i] = pool.state_slot(seq.sid)
        if slots is not None and pool.slot_bytes > self._ring_bytes:
            # the recurrent state of the step's lanes: read once, written
            # once
            more["state_bytes"] = len(lanes) * (pool.slot_bytes
                                                - self._ring_bytes)
        if self._ring_bytes:
            # the rings of the step's lanes: fetched whole whatever the
            # lane's length, one row of each written
            more["window_bytes"] = len(lanes) * self._ring_bytes
            self.metrics.g_window_bytes.set(more["window_bytes"])
        if self._latent_token_bytes:
            # the latent rows of the lanes' tokens up to this step's: what
            # the absorbed attention reads
            more["latent_bytes"] = self._latent_token_bytes * sum(
                s.next_pos + 1 for s in lanes)
            self.metrics.g_latent_bytes.set(more["latent_bytes"])
        self._describe_step(span, lanes, b, inflight=int(prev is not None),
                            fed_device=int((source >= 0).sum()), **more)
        outs = self._dispatch_lanes(self._decode[b], data, positions, table,
                                    source,
                                    **({} if slots is None
                                       else {"slots": slots}))
        self.metrics.steps.inc()
        if prev is not None:
            self.metrics.steps_overlapped.inc()
        extras = outs[len(outs) - len(self._lane_extras):]
        flight = _Flight(self._decode[b], [(s, s.next_pos) for s in lanes],
                         self._picked(outs)._data,
                         {name: out._data for name, out
                          in zip(self._lane_extras, extras)})
        for seq in lanes:
            seq.next_pos += 1
        return flight

    def _read_step(self, flight: Optional[_Flight], span):
        """Read the ids a dispatched step picked (``None``: nothing was in
        flight, nothing is read) and stream the token of every lane whose
        cursor crossed into generation; a lane re-walking a known suffix
        (partial cache hit, re-admitted preemptee) just materialized K/V.
        A lane that left since the dispatch (an EOS seen a step late, a
        preemption, a hand-off) rode the step for nothing: its token is
        dropped, and greedy decode computes it again if it is needed."""
        lanes, ids, extras = \
            (flight.lanes, flight.ids, flight.extras) if flight is not None \
            else ((), np.zeros((0,), _CARRIER), {})
        # the read blocks until the device has run the step
        with _span("gen:pool_d2h", "gen") as d2h:
            ids = np.asarray(ids)
            extras = {name: np.asarray(v) for name, v in extras.items()}
            d2h.set(bytes=ids.nbytes
                    + sum(v.nbytes for v in extras.values()))
        if "expert_load" in extras:
            self._note_expert_load(extras["expert_load"], span)
        with _span("gen:emit", "gen") as emit:
            retired = []
            emitted = dropped = 0
            for i, (seq, pos) in enumerate(lanes):
                emits = pos + 1 >= len(seq.tokens)
                if seq not in self._active:
                    dropped += emits
                    continue
                seq.iters += 1
                if self.prefix_cache_pages:
                    self.pool.register_prefix(seq.sid, seq.tokens[:pos + 1])
                if emits:
                    emitted += 1
                    if self._emit(seq, int(ids[i])):
                        retired.append(seq)
            self._drop_retired(retired)
            self.metrics.tokens_dropped.inc(dropped)
            emit.set(emitted=emitted, retired=len(retired))
        span.set(dropped=dropped)

    def _note_expert_load(self, load, span):
        """``load`` (expert layers, experts): the live lanes' picks in the
        step just read, over the router's whole width.  On the span that
        read it: the experts HELD HERE with at least one pick (summed over
        the layers; a family that holds a share of the experts fetches no
        other), the (lane, pick) pairs, those of them that land on held
        experts (what the grouped products multiply), and the bytes of the
        hit experts' weights -- what a kernel that skips idle experts would
        fetch."""
        first = int(getattr(self.family, "first_expert", 0))
        held = getattr(self.family, "experts_held", None) or load.shape[1]
        mine = load[:, first:first + held]
        hit = int((mine > 0).sum())
        pairs, pairs_held = int(load.sum()), int(mine.sum())
        span.set(experts_hit=hit, expert_pairs=pairs,
                 expert_pairs_held=pairs_held,
                 expert_bytes=hit * self.family.expert_bytes())
        with self._cv:
            self._expert_load = load.astype(np.int64) + (
                0 if self._expert_load is None else self._expert_load)
            self._expert_steps += 1
            self._experts_hit_total += hit
        self.metrics.expert_picks.inc(pairs)
        self.metrics.g_experts_hit.set(hit)
        self.metrics.g_expert_pairs_held.set(pairs_held)

    def _spec_step(self, active: List[_Seq], span):
        """One speculative iteration: draft K proposals per steady lane,
        then ONE windowed target verify pass scores feed slots
        ``[tokens[next_pos], d_1 .. d_K]`` at positions ``next_pos ..
        next_pos+K`` (teacher forcing — every feed token is known before
        the call, so the graph is the same single causal pass as
        catch-up, not K+1 chained decode blocks).  Greedy acceptance
        walks the slots in order, keeping every emitted argmax whose
        following draft feed matches — the emitted tokens are the
        TARGET's own argmaxes over the same paged K/V a plain decode
        would read, and the spec-parity tests assert transcript equality
        against non-speculative greedy for every K.  A fault at
        ``generation.draft.verify`` degrades THIS iteration to a plain
        single-token step instead of failing any stream."""
        width = self._verify_width
        b = self._lane_bucket_for(len(active))
        self._note_lane_bucket(b)
        try:
            faults.fire("generation.draft.verify")
        except Exception:
            self.metrics.spec_fallbacks.inc()
            self._read_step(self._dispatch_step(active, b, None, span), span)
            return
        self._describe_step(span, active, b, inflight=0, fed_device=0,
                            dropped=0)
        with _span("gen:draft", "gen", {"k": width - 1}):
            proposals = self._draft_propose(active, b)
        vpred = self._verify[b]
        data = np.zeros((b, width), _CARRIER)
        # pad slots park at (token 0, position max_seq_len-1): with a
        # zero page-table row beyond the lane's allocation the write
        # lands in scratch page 0, and no live position ever attends it
        positions = np.full((b, width), self.max_seq_len - 1, _CARRIER)
        table = np.zeros((b, self.max_pages), _CARRIER)
        lane_width: Dict[object, int] = {}
        for i, seq in enumerate(active):
            table[i] = self.pool.page_table_row(seq.sid, self.max_pages)
            drafts = proposals.get(seq.sid, [])
            lw = 0
            for w in range(width):
                p = seq.next_pos + w
                if p >= min(seq.limit, self.max_seq_len):
                    break
                if p < len(seq.tokens):
                    tok = seq.tokens[p]
                else:
                    j = w - (len(seq.tokens) - seq.next_pos)
                    if j < 0 or j >= len(drafts):
                        break
                    tok = drafts[j]
                data[i, w] = tok
                positions[i, w] = p
                lw += 1
            lane_width[seq.sid] = lw
        with _span("gen:verify", "gen", {"width": width}):
            logits = self._run_lanes(vpred, data, positions, table)
        logits = logits.reshape(b, width, -1)
        self.metrics.steps.inc()
        retired = []
        for i, seq in enumerate(active):
            seq.iters += 1
            lw = lane_width[seq.sid]
            n_drafted = max(0, lw - (len(seq.tokens) - seq.next_pos))
            start = seq.next_pos
            emits = 0
            for w in range(lw):
                g = int(np.argmax(logits[i, w]))
                seq.next_pos = start + w + 1
                if seq.next_pos < len(seq.tokens):
                    continue  # known-suffix slot: K/V only, no emission
                emits += 1
                if self._emit(seq, g):
                    retired.append(seq)
                    break
                if w + 1 < lw and int(data[i, w + 1]) != g:
                    break  # draft diverged: discard the rest
            if self.prefix_cache_pages:
                self.pool.register_prefix(seq.sid,
                                          seq.tokens[:seq.next_pos])
            # the draft pool holds accepted-token K/V below next_pos and
            # rejected junk above it: snap the cursor back so the next
            # sync round re-feeds only what the target actually kept
            seq.draft_pos = seq.next_pos
            if n_drafted:
                accepted = max(0, emits - 1)
                self.metrics.draft_proposed.inc(n_drafted)
                self.metrics.draft_accepted.inc(accepted)
                rate = accepted / float(n_drafted)
                st = seq.stream
                st.draft_proposed += n_drafted
                st.draft_accepted += accepted
                st.accept_rate = (rate if st.accept_rate is None
                                  else 0.8 * st.accept_rate + 0.2 * rate)
                self._accept_ewma = (rate if self._accept_ewma is None
                                     else 0.8 * self._accept_ewma
                                     + 0.2 * rate)
                self.metrics.g_accept.set(self._accept_ewma)
        self._drop_retired(retired)

    def _draft_propose(self, active: List[_Seq], b: int) -> Dict:
        """Run the draft model: first catch its pool up to each lane's
        feed cursor (re-feeding accepted tokens its last rejected run
        clobbered), then K batched rounds of chained greedy proposals
        for every steady lane.  Returns {sid: [d_1 .. d_K]}."""
        k = self._verify_width - 1
        pred = self._draft_decode[b]
        rows = {s.sid: self._draft_pool.page_table_row(s.sid,
                                                       self.max_pages)
                for s in active}
        while True:
            lag = [s for s in active if s.draft_pos < s.next_pos]
            if not lag:
                break
            data = np.zeros((b,), _CARRIER)
            positions = np.full((b,), self.max_seq_len - 1, _CARRIER)
            table = np.zeros((b, self.max_pages), _CARRIER)
            for i, seq in enumerate(active):
                if seq.draft_pos < seq.next_pos:
                    data[i] = seq.tokens[seq.draft_pos]
                    positions[i] = seq.draft_pos
                    table[i] = rows[seq.sid]
            self._run_lanes(pred, data, positions, table)
            for seq in lag:
                seq.draft_pos += 1
        proposals: Dict[object, List[int]] = {}
        feed: Dict[object, int] = {}
        for seq in active:
            if seq.next_pos == len(seq.tokens) - 1:
                proposals[seq.sid] = []
                feed[seq.sid] = seq.tokens[seq.next_pos]
        if not proposals:
            return proposals
        for r in range(k):
            data = np.zeros((b,), _CARRIER)
            positions = np.full((b,), self.max_seq_len - 1, _CARRIER)
            table = np.zeros((b, self.max_pages), _CARRIER)
            live = []
            for i, seq in enumerate(active):
                if seq.sid not in proposals:
                    continue
                p = seq.next_pos + r
                if p >= min(seq.limit, self.max_seq_len) - 1:
                    continue  # no use drafting past the hard stop
                data[i] = feed[seq.sid]
                positions[i] = p
                table[i] = rows[seq.sid]
                live.append((i, seq))
            if not live:
                break
            logits = self._run_lanes(pred, data, positions, table)
            for i, seq in live:
                d = int(np.argmax(logits[i]))
                proposals[seq.sid].append(d)
                feed[seq.sid] = d
        return proposals

    def _drop_retired(self, retired: List[_Seq]):
        if not retired:
            return
        with self._cv:
            for seq in retired:
                if seq in self._active:
                    self._active.remove(seq)
            self.metrics.g_active.set(len(self._active))
            self._cv.notify_all()
