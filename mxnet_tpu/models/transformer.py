"""Decoder-only transformer LM symbol builders — the TPU-native flagship
model family (beyond the 2017 reference, which predates transformers; its
sequence-model slot was the RNN stack, rnn/rnn_cell.py).

ONE pre-norm block (``_block``: LayerNorm, fused q/k/v projection,
attention, output projection, residual; LayerNorm, a 4 x hidden MLP,
residual), one ``_embed`` and one ``_head``.  Four graphs are assembled
from them, and a change to the model is a change to those three:

* ``get_transformer_lm``: training, the flash kernels (ops/attention.py);
* ``get_transformer_lm_prefill``: a prompt bucket, dense attention, the
  layers' K/V beside the logits;
* ``get_transformer_lm_decode`` / ``get_transformer_lm_catchup``: the lane
  program over the paged KV pool, one token a lane or a window of them.

The block is told its layout (``seq_len`` for ``(b, seq_len, hidden)``,
``None`` for rows) and how to attend, and nothing else.  All four bind one
training checkpoint: the parameter names are the block's.  The Symbols'
``tojson()`` is the compile-cache fingerprint (generation/engine.py), so
the nodes are created in a fixed order: unnamed ``Reshape``s draw their
names from a counter (tests/test_transformer.py pins the digests).

The lane program's contract: inputs ``data``, ``positions``,
``page_table`` and the planes :func:`lane_plane_names` lists, each
``(num_pages, page_size, heads, head_dim)``; outputs the logits, then the
updated planes in that same order.  Every shape comes from the bind, so
one Symbol serves every lane count, pool size and window width.  The
decode graph also picks: its last output ``next_ids`` is the greedy token
of every lane, and its inputs ``prev_ids`` (the ``next_ids`` of the step
before) and ``source`` let a lane take its token from there, so that the
token need not cross the host boundary between two steps.
"""

from .. import symbol as sym


def _fc(x, n_in, n_out, name, seq_len):
    """FullyConnected is 2-D (reference fully_connected-inl.h): the
    sequence layout is flattened to rows first, rows go as they are."""
    if seq_len is not None:
        x = sym.Reshape(x, shape=(-1, n_in))
    return sym.FullyConnected(x, num_hidden=n_out, name=name)


def _block(x, hidden, num_heads, name, attend, seq_len=None):
    """The decoder block.  ``attend(q, k, v, name) -> (att, extras)``;
    returns the block's output and ``attend``'s extras."""
    lead = (-1,) if seq_len is None else (-1, seq_len)

    def residual(x, h, suffix):
        if seq_len is not None:
            h = sym.Reshape(h, shape=lead + (hidden,))
        return sym.broadcast_add(x, h, name=name + suffix)

    # attention sublayer (pre-norm)
    h = sym.LayerNorm(x, name="%s_ln1" % name)
    qkv = _fc(h, hidden, 3 * hidden, "%s_qkv" % name, seq_len)
    qkv = sym.Reshape(qkv, shape=lead + (3, num_heads, hidden // num_heads))
    q, k, v = sym.SliceChannel(qkv, num_outputs=3, axis=len(lead),
                               squeeze_axis=True, name="%s_split" % name)
    att, extras = attend(q, k, v, "%s_attn" % name)
    att = sym.Reshape(att, shape=lead + (hidden,))
    x = residual(x, _fc(att, hidden, hidden, "%s_proj" % name, seq_len),
                 "_res1")
    # mlp sublayer (pre-norm)
    h = sym.LayerNorm(x, name="%s_ln2" % name)
    h = _fc(h, hidden, 4 * hidden, "%s_fc1" % name, seq_len)
    h = sym.gelu(h, name="%s_gelu" % name)
    h = _fc(h, 4 * hidden, hidden, "%s_fc2" % name, seq_len)
    return residual(x, h, "_res2"), extras


def _embed(data, vocab_size, hidden, max_seq_len, seq_len=None,
           positions=None):
    """Token plus learned position embedding: the first ``seq_len`` rows
    of the table in the sequence layout, ``take`` by ``positions`` for
    rows."""
    pos = sym.Variable("pos_embed_weight", shape=(1, max_seq_len, hidden))
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=hidden,
                      name="tok_embed")
    if seq_len is None:
        pos = sym.Reshape(pos, shape=(max_seq_len, hidden), name="pos_flat")
        pos = sym.take(pos, positions, name="pos_take")
    elif seq_len != max_seq_len:
        pos = sym.slice_axis(pos, axis=1, begin=0, end=seq_len,
                             name="pos_slice")
    return sym.broadcast_add(x, pos, name="pos_add")


def _head(x, hidden, vocab_size, seq_len=None):
    """Final norm and the vocabulary projection: logits by rows."""
    x = sym.LayerNorm(x, name="ln_f")
    return _fc(x, hidden, vocab_size, "lm_head", seq_len)


def get_transformer_lm(vocab_size=32000, num_layers=4, num_heads=8,
                       hidden=512, seq_len=128, block_q=None, block_k=None,
                       attn_impl="flash"):
    """Causal LM: data (b, seq_len) token ids -> SoftmaxOutput over the
    vocab at every position (label (b*seq_len,) next-token ids)."""
    # ``attn_impl`` chooses nothing: the flash kernels are the one training
    # attention.  The keyword stays because perfbench/builders/gpt2_lm.py
    # passes it and only a `benchmark` PR may edit that file (PERF.md
    # section 7); it goes with those two call sites.
    if attn_impl != "flash":
        raise ValueError("attn_impl must be 'flash', got %r" % (attn_impl,))

    def flash(q, k, v, name):
        return sym._contrib_FlashAttention(q, k, v, causal=True,
                                           block_q=block_q, block_k=block_k,
                                           name=name), None

    x = _embed(sym.Variable("data"), vocab_size, hidden, seq_len, seq_len)
    for i in range(num_layers):
        x, _ = _block(x, hidden, num_heads, "layer%d" % i, flash, seq_len)
    logits = _head(x, hidden, vocab_size, seq_len)  # (b*s, vocab)
    # label arrives (b, seq_len) from the iterator; flatten inside the
    # symbol like the reference LM examples (example/rnn/lstm_bucketing.py)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")


def get_transformer_lm_prefill(vocab_size=32000, num_layers=4, num_heads=8,
                               hidden=512, seq_len=128, max_seq_len=None):
    """Prefill pass for generation: ``data`` (b, seq_len) token ids ->
    ``Group([logits, k0, v0, k1, v1, ...])`` with logits (b, seq_len,
    vocab) and per-layer K/V (b, seq_len, heads, head_dim), which the
    engine writes into the paged pool so decode never recomputes the prefix.

    ``seq_len`` is this executable's (bucketed) prompt capacity;
    ``max_seq_len`` (default ``seq_len``) is the position-table capacity
    shared with the training symbol — the engine builds one prefill
    executor per length bucket against one ``pos_embed_weight``.
    Prompts shorter than ``seq_len`` are right-padded by the caller;
    causal attention keeps the padding from contaminating real
    positions, so only outputs at < length are meaningful."""
    def dense(q, k, v, name):
        # dense oracle attention: prefill runs once per sequence and must
        # be CPU-fast (interpret-mode Pallas is not); whether a TPU should
        # take the flash kernels here is ROADMAP.md D14's measurement
        return sym._contrib_DenseAttention(q, k, v, causal=True,
                                           name=name), [k, v]

    x = _embed(sym.Variable("data"), vocab_size, hidden,
               max_seq_len or seq_len, seq_len)
    kvs = []
    for i in range(num_layers):
        x, kv = _block(x, hidden, num_heads, "layer%d" % i, dense, seq_len)
        kvs.extend(kv)
    logits = sym.Reshape(_head(x, hidden, vocab_size, seq_len),
                         shape=(-1, seq_len, vocab_size), name="logits")
    return sym.Group([logits] + kvs)


def lane_plane_names(num_layers):
    """The lane program's KV planes, in the order it takes them as
    arguments and hands them back after the logits."""
    return ["layer%d_%s_pool" % (i, kv)
            for i in range(num_layers) for kv in "kv"]


def _lane_graph(vocab_size, num_layers, num_heads, hidden, max_seq_len,
                page_size, window):
    data = sym.Variable("data")
    positions = sym.Variable("positions")
    page_table = sym.Variable("page_table")
    paged = (sym._contrib_PagedAttentionWindow if window
             else sym._contrib_PagedAttention)

    def over(k_plane, v_plane):
        def attend(q, k, v, name):
            att, k_out, v_out = paged(
                q, k, v, sym.Variable(k_plane), sym.Variable(v_plane),
                page_table, positions, page_size=page_size, name=name)
            return att, [k_out, v_out]
        return attend

    rows, row_positions = data, positions
    if window:  # (lanes, width) -> lanes * width rows, a lane's together
        rows = sym.Reshape(data, shape=(-1,), name="tok_flat")
        row_positions = sym.Reshape(positions, shape=(-1,),
                                    name="pos_ids_flat")
    else:
        # source[i] >= 0: lane i feeds what lane source[i] of the step
        # before picked; below 0 it feeds data[i] (take clips the index)
        source = sym.Variable("source")
        rows = sym.where(
            sym._greater_equal_scalar(source, scalar=0, name="from_prev"),
            sym.take(sym.Variable("prev_ids"), source, name="prev_take"),
            data, name="fed_ids")
    x = _embed(rows, vocab_size, hidden, max_seq_len,
               positions=row_positions)
    names = lane_plane_names(num_layers)
    planes_out = []
    for i, layer_planes in enumerate(zip(names[::2], names[1::2])):
        x, planes = _block(x, hidden, num_heads, "layer%d" % i,
                           over(*layer_planes))
        planes_out.extend(planes)
    logits = _head(x, hidden, vocab_size)
    if window:
        return sym.Group([logits] + planes_out)
    # greedy, on the device: the first maximum of the float32 row, as
    # np.argmax takes it; in the ids' carrier dtype (vocab < 2**24 is exact)
    next_ids = sym.argmax(logits, axis=-1, name="next_ids")
    return sym.Group([logits] + planes_out + [next_ids])


def get_transformer_lm_decode(vocab_size=32000, num_layers=4, num_heads=8,
                              hidden=512, max_seq_len=128, page_size=16):
    """One incremental decode step over paged KV: every lane advances one
    token, reading and writing fixed-size KV pages through its page-table
    row instead of recomputing the prefix (``_contrib_PagedAttention``).

    ``data``, ``positions``, ``source`` and ``prev_ids`` are (lanes,),
    ``page_table`` (lanes, max_pages), logits (lanes, vocab), ``next_ids``
    (lanes,); the rest is the module's lane contract.  Everything is
    static-shape, so one executable per lane count serves any mix of
    sequence lengths — the continuous-batching contract."""
    return _lane_graph(vocab_size, num_layers, num_heads, hidden,
                       max_seq_len, page_size, window=False)


def get_transformer_lm_catchup(vocab_size=32000, num_layers=4, num_heads=8,
                               hidden=512, max_seq_len=128, page_size=16):
    """Windowed teacher-forcing pass: ``width`` KNOWN tokens per lane
    advance in ONE forward over paged KV.  The tokens come from a
    prefix-cache hit's suffix, a re-admitted preemptee's transcript, or
    a speculative draft's proposals (the engine's verify rig) — in every
    case nothing has to wait for the previous slot's argmax, so this is a
    single causal pass: every projection runs batched over ``lanes *
    width`` rows and each layer gathers the paged history once
    (``_contrib_PagedAttentionWindow``), so the cost scales like a short
    prefill instead of ``width`` decode steps.  It writes the same K/V
    slots and attends the same masked history as decode, and the engine's
    parity tests assert transcript equality against it.

    ``data`` and ``positions`` are (lanes, width) (pad slots at
    ``max_seq_len - 1`` with a zero page-table row park in scratch);
    logits are (lanes * width, vocab) — row ``lane * width + w`` scores
    window slot ``w``."""
    return _lane_graph(vocab_size, num_layers, num_heads, hidden,
                       max_seq_len, page_size, window=True)


class TransformerLMFamily:
    """What the generation engine asks of a model family, answered for
    this module's block (generation/engine.py, "The family seam"): the
    graphs it has and the planes they carry.  ``dtype`` is the K/V planes'.
    """

    name = "transformer_lm"
    prefill_inputs = ("data",)

    def __init__(self, vocab_size, num_layers, num_heads, hidden,
                 dtype="float32"):
        self.vocab_size, self.num_layers = int(vocab_size), int(num_layers)
        self.num_heads, self.hidden = int(num_heads), int(hidden)
        self.kv_heads, self.head_dim = self.num_heads, hidden // num_heads
        self.dtype = str(dtype)
        self._sizes = dict(vocab_size=self.vocab_size,
                           num_layers=self.num_layers,
                           num_heads=self.num_heads, hidden=self.hidden)

    def spec(self):
        return dict(self._sizes, family=self.name, dtype=self.dtype)

    def engine_spec(self):
        """The keys this family adds to ``DecodeEngine.spec()``: the
        engine's own width keywords, as before there were families."""
        return dict(self._sizes)

    def planes(self):
        """(name, kind, shape of a token's entry, dtype) of every carried
        plane, in the lane program's order."""
        return [(name, "paged", (self.kv_heads, self.head_dim), self.dtype)
                for name in lane_plane_names(self.num_layers)]

    def prefill_symbol(self, seq_len, max_seq_len):
        return get_transformer_lm_prefill(seq_len=seq_len,
                                          max_seq_len=max_seq_len,
                                          **self._sizes)

    def decode_symbol(self, max_seq_len, page_size):
        return get_transformer_lm_decode(max_seq_len=max_seq_len,
                                         page_size=page_size, **self._sizes)

    def catchup_symbol(self, max_seq_len, page_size):
        return get_transformer_lm_catchup(max_seq_len=max_seq_len,
                                          page_size=page_size, **self._sizes)
