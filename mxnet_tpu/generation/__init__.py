"""Generative serving — continuous batching + paged KV-cache.

``kv_pool``: fixed-size KV pages + per-sequence page tables, so KV
memory scales with live tokens instead of max_len x batch.
and per-lane slots of recurrent state beside them (one manager).
``engine``: :class:`DecodeEngine`, iteration-level continuous batching
over fixed-shape per-lane-bucket decode executables (admit/retire every
step, zero post-warmup recompiles, streaming :class:`GenStream`
handles).  Token-path optimizations: cross-request prefix caching
(content-hashed copy-on-write KV pages, ``MXNET_GEN_PREFIX_CACHE_PAGES``)
and speculative decoding (draft model + fused verify pass, bit-identical
greedy acceptance).  Serving integration
(``generate`` SLO class, ``POST /generate`` token streaming) lives in
``mxnet_tpu.serving``.
"""
from .engine import DecodeEngine, GenStream, StateNotRebuildableError
from .kv_pool import KVPoolExhaustedError, PagedKVPool

__all__ = ["DecodeEngine", "GenStream", "PagedKVPool",
           "KVPoolExhaustedError", "StateNotRebuildableError"]
