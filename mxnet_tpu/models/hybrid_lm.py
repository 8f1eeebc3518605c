"""Hybrid decoder-only LM: state-space (Mamba-2) layers with a few
grouped-query attention layers between them, no positional encoding
(IBM Granite 4.0-H; ``granitemoehybrid`` with no experts).

ONE description of the model (:class:`HybridLM`: the layer pattern and the
sizes) and three graphs assembled from one block, as ``transformer.py``
assembles its own:

* :func:`get_hybrid_lm`: a whole sequence, ``SoftmaxOutput`` over the
  vocabulary at every position (scoring);
* :func:`get_hybrid_lm_prefill`: a prompt bucket with the prompts' true
  lengths; beside the logits it returns what every layer carries into
  decode, in :meth:`HybridLM.planes`' order: an attention layer its K and
  V ``(b, L, kv_heads, head_dim)``, a state-space layer its recurrent
  state ``(b, heads, head_dim, state)`` and convolution tail ``(b, K-1,
  conv_dim)`` as they stand after each prompt's LAST REAL token;
* :func:`get_hybrid_lm_decode`: the lane program, one token a lane: the
  attention layers read and write K/V pages through ``page_table``, the
  state-space layers read and write their lane's slot of the state planes
  through ``state_slot``; it picks and feeds on as the transformer's does
  (``source`` / ``prev_ids`` / ``next_ids``).

The model, for layer ``l`` with ``m`` the residual multiplier::

    h0 = embedding_multiplier * E[ids]
    a  = x + m * Mixer_l(RMSNorm(x; norm1))
    x' = a + m * W_out(silu(g) * u),   [g | u] = W_in RMSNorm(a; norm2)
    logits = RMSNorm(x; norm_f) E^T / logits_scaling

Attention mixer: q/k/v/o without bias, NO positional encoding, softmax of
``q k^T * attention_multiplier``, query head ``i`` over K/V head ``i //
group``.  State-space mixer: ``[z | xBC | dt] = W_in_proj h``; ``xBC``
through the causal convolution and SiLU; the selective scan (ops/ssm.py);
``RMSNorm(y * silu(z); gate_norm)``; ``W_out_proj``.  Activations take the
weights' dtype (the embedding's); norms, softmax, ``dt``, the recurrence and
its state are float32; the logits are float32.

There is no windowed (catch-up / verify) graph: a recurrent state cannot be
rewound or rebuilt from cached pages, and the engine refuses what would
need one by name (generation/engine.py).
"""
from .. import symbol as sym

MAMBA, ATTENTION = "mamba", "attention"


class HybridLM:
    """The model's description, and the family object the generation
    engine asks (generation/engine.py, "The family seam").

    ``layer_types`` is the pattern (``"mamba"`` / ``"attention"`` a
    layer); ``num_heads`` / ``kv_heads`` / ``head_dim`` the attention
    layers'; ``intermediate`` the gated MLP's inner width; ``ssm_heads`` /
    ``ssm_head_dim`` / ``ssm_state`` / ``conv_kernel`` / ``chunk`` the
    state-space layers' (one B/C group); ``dtype`` the K/V planes' and the
    convolution tails' (the weights'); the recurrent state is float32.
    """

    name = "hybrid_lm"
    prefill_inputs = ("data", "length")
    _FIELDS = dict(vocab_size=None, hidden=None, layer_types=None,
                   num_heads=None, kv_heads=None, head_dim=None,
                   intermediate=None, ssm_heads=None, ssm_head_dim=None,
                   ssm_state=None, conv_kernel=4, chunk=256, eps=1e-5,
                   embedding_multiplier=1.0, residual_multiplier=1.0,
                   attention_multiplier=None, logits_scaling=1.0,
                   dtype="bfloat16")

    def __init__(self, **sizes):
        sizes.pop("family", None)
        unknown = set(sizes) - set(self._FIELDS)
        missing = [k for k, v in self._FIELDS.items()
                   if v is None and sizes.get(k) is None
                   and k != "attention_multiplier"]
        if unknown or missing:
            raise ValueError("HybridLM: unknown %s, missing %s"
                             % (sorted(unknown), missing))
        for k, default in self._FIELDS.items():
            setattr(self, k, sizes.get(k, default))
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {MAMBA, ATTENTION}
        if bad or ATTENTION not in self.layer_types:
            raise ValueError("layer_types: every entry %r or %r, at least "
                             "one attention layer (the engine's pages); "
                             "got %s" % (MAMBA, ATTENTION, sorted(bad)))
        if self.attention_multiplier is None:
            self.attention_multiplier = float(self.head_dim) ** -0.5
        self.ssm_inner = self.ssm_heads * self.ssm_head_dim
        self.conv_dim = self.ssm_inner + 2 * self.ssm_state
        self.num_layers = len(self.layer_types)

    def spec(self):
        out = {k: getattr(self, k) for k in self._FIELDS}
        return dict(out, layer_types=list(self.layer_types),
                    family=self.name)

    def engine_spec(self):
        """The keys this family adds to ``DecodeEngine.spec()``."""
        return {"family": self.spec()}

    def planes(self):
        """(name, kind, shape of one entry, dtype) of every carried plane,
        in the order the lane program takes and returns them and the
        prefill graph returns their slabs."""
        out = []
        for i, kind in enumerate(self.layer_types):
            if kind == ATTENTION:
                out += [("layer%d_%s_pool" % (i, kv), "paged",
                         (self.kv_heads, self.head_dim), self.dtype)
                        for kv in "kv"]
            else:
                out += [("layer%d_ssm_state" % i, "slot",
                         (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                         "float32"),
                        ("layer%d_conv_tail" % i, "slot",
                         (self.conv_kernel - 1, self.conv_dim), self.dtype)]
        return out

    def prefill_symbol(self, seq_len, max_seq_len=None):
        return get_hybrid_lm_prefill(self, seq_len)

    def decode_symbol(self, max_seq_len=None, page_size=16):
        return get_hybrid_lm_decode(self, page_size)

    def catchup_symbol(self, max_seq_len=None, page_size=16):
        return None  # see the module's last paragraph


def _fc(x, n_out, name):
    return sym.FullyConnected(x, num_hidden=n_out, no_bias=True, name=name)


def _vec(name, n):
    return sym.Variable(name, shape=(n,))


def _norm(x, m, name):
    return sym._contrib_RMSNorm(x, _vec(name + "_gamma", m.hidden),
                                eps=m.eps, name=name)


def _residual(x, h, m, name):
    return sym.elemwise_add(
        x, sym._mul_scalar(h, scalar=m.residual_multiplier), name=name)


def _mamba_mixer(h, m, name, seq_len, carried):
    """``carried``: the sequence layout's ``length`` Symbol or None, or the
    lane layout's ``(state plane, tail plane, state_slot)``.  Returns the
    mixer's output rows and [state, tail] (finals, or the planes' next)."""
    inner, heads = m.ssm_inner, m.ssm_heads
    zxbcdt = _fc(h, 2 * inner + 2 * m.ssm_state + heads, name + "_in_proj")
    z = sym.slice_axis(zxbcdt, axis=-1, begin=0, end=inner, name=name + "_z")
    xbc = sym.slice_axis(zxbcdt, axis=-1, begin=inner,
                         end=inner + m.conv_dim, name=name + "_xbc")
    dt = sym.slice_axis(zxbcdt, axis=-1, begin=inner + m.conv_dim,
                        end=inner + m.conv_dim + heads, name=name + "_dt")
    conv = [sym.Variable(name + "_conv_weight",
                         shape=(m.conv_dim, m.conv_kernel)),
            _vec(name + "_conv_bias", m.conv_dim)]
    ssm = [_vec(name + "_A_log", heads), _vec(name + "_D", heads),
           _vec(name + "_dt_bias", heads)]
    sizes = dict(heads=heads, head_dim=m.ssm_head_dim, state=m.ssm_state)
    if seq_len is not None:
        more = [] if carried is None else [carried]
        xbc = sym.Reshape(xbc, shape=(-1, seq_len, m.conv_dim))
        dt = sym.Reshape(dt, shape=(-1, seq_len, heads))
        xbc, tail = sym._contrib_CausalConv1D(
            xbc, *(conv + more), use_length=bool(more), name=name + "_conv")
        y, state = sym._contrib_SSMScan(
            xbc, dt, *(ssm + more), use_length=bool(more), chunk=m.chunk,
            name=name + "_ssm", **sizes)
        y = sym.Reshape(y, shape=(-1, inner))
    else:
        states, tails, slot = carried
        xbc, tail = sym._contrib_CausalConv1DStep(
            xbc, *(conv + [tails, slot]), name=name + "_conv")
        y, state = sym._contrib_SSMStep(
            xbc, dt, *(ssm + [states, slot]), name=name + "_ssm", **sizes)
    y = sym._contrib_GatedRMSNorm(y, z, _vec(name + "_gate_norm_gamma", inner),
                                  eps=m.eps, name=name + "_gate_norm")
    return _fc(y, m.hidden, name + "_out_proj"), [state, tail]


def _attention_mixer(h, m, name, seq_len, attend):
    """``attend(q, k, v, name) -> (att, extras)`` over ``(..., heads,
    head_dim)`` / ``(..., kv_heads, head_dim)``."""
    lead = (-1,) if seq_len is None else (-1, seq_len)
    hd = m.head_dim

    def heads(x, n):
        return sym.Reshape(x, shape=lead + (n, hd))

    q = heads(_fc(h, m.num_heads * hd, name + "_q"), m.num_heads)
    k = heads(_fc(h, m.kv_heads * hd, name + "_k"), m.kv_heads)
    v = heads(_fc(h, m.kv_heads * hd, name + "_v"), m.kv_heads)
    att, extras = attend(q, k, v, name + "_attn")
    att = sym.Reshape(att, shape=(-1, m.num_heads * hd))
    return _fc(att, m.hidden, name + "_o"), extras


def _block(x, m, i, seq_len, attend, carried):
    """Layer ``i`` over rows ``x`` (every position of every sequence, or
    every lane): returns the rows and what the layer carries."""
    name = "layer%d" % i
    h = _norm(x, m, name + "_norm1")
    if m.layer_types[i] == ATTENTION:
        h, extras = _attention_mixer(h, m, name, seq_len, attend)
    else:
        h, extras = _mamba_mixer(h, m, name, seq_len, carried)
    x = _residual(x, h, m, name + "_res1")
    h = _norm(x, m, name + "_norm2")
    h = _fc(h, 2 * m.intermediate, name + "_mlp_in")
    h = sym._contrib_SiluGate(h, name=name + "_mlp_gate")
    h = _fc(h, m.hidden, name + "_mlp_out")
    return _residual(x, h, m, name + "_res2"), extras


def _embed(ids, m, table):
    x = sym.Embedding(ids, weight=table, input_dim=m.vocab_size,
                      output_dim=m.hidden, name="tok_embed")
    x = sym._mul_scalar(x, scalar=m.embedding_multiplier, name="embed_scale")
    return sym.Reshape(x, shape=(-1, m.hidden), name="embed_rows")


def _head(x, m, table):
    """Final norm and the tied vocabulary projection: float32 logits by
    rows."""
    x = _norm(x, m, "norm_f")
    return sym._contrib_ScaledLogits(x, table, scale=1.0 / m.logits_scaling,
                                     name="lm_head")


def _table(m):
    return sym.Variable("tok_embed_weight", shape=(m.vocab_size, m.hidden))


def _sequence_graph(m, seq_len, length):
    def dense(q, k, v, name):
        return sym._contrib_DenseAttention(
            q, k, v, causal=True, scale=m.attention_multiplier,
            name=name), [k, v]

    table = _table(m)
    x = _embed(sym.Variable("data"), m, table)
    carried = []
    for i in range(m.num_layers):
        x, extras = _block(x, m, i, seq_len, dense, length)
        carried.extend(extras)
    return _head(x, m, table), carried


def get_hybrid_lm(model, seq_len):
    """Causal LM over whole rows: ``data`` (b, seq_len) token ids ->
    SoftmaxOutput over the vocabulary at every position (label (b,
    seq_len) next-token ids), as ``get_transformer_lm``."""
    logits, _ = _sequence_graph(model, seq_len, None)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return sym.SoftmaxOutput(logits, label=label, name="softmax")


def get_hybrid_lm_prefill(model, seq_len):
    """Prefill of one prompt-length bucket: ``data`` (b, seq_len) right-
    padded token ids and ``length`` (b,) the prompts' true lengths ->
    ``Group([logits (b, seq_len, vocab)] + the planes' slabs)`` (module
    docstring).  Causal attention and the scan's ``length`` keep padding
    from reaching a real position or the final state."""
    logits, carried = _sequence_graph(model, seq_len, sym.Variable("length"))
    logits = sym.Reshape(logits, shape=(-1, seq_len, model.vocab_size),
                         name="logits")
    return sym.Group([logits] + carried)


def get_hybrid_lm_decode(model, page_size=16):
    """One decode step, every lane one token.  Inputs ``data``,
    ``positions``, ``source``, ``prev_ids``, ``state_slot`` (lanes,),
    ``page_table`` (lanes, max_pages) and the planes of
    :meth:`HybridLM.planes`; outputs the logits (lanes, vocab), the planes
    in that order, then ``next_ids`` (lanes,).  ``positions`` places the
    attention layers' K/V only: the model has no positional encoding."""
    m = model
    data, positions = sym.Variable("data"), sym.Variable("positions")
    page_table, slot = sym.Variable("page_table"), sym.Variable("state_slot")
    # as transformer._lane_graph: a lane takes its token from the step
    # before on the device, or from the host
    source = sym.Variable("source")
    ids = sym.where(
        sym._greater_equal_scalar(source, scalar=0, name="from_prev"),
        sym.take(sym.Variable("prev_ids"), source, name="prev_take"),
        data, name="fed_ids")
    planes = {name: sym.Variable(name) for name, _, _, _ in m.planes()}

    def paged(i):
        def attend(q, k, v, name):
            att, k_out, v_out = sym._contrib_PagedAttention(
                q, k, v, planes["layer%d_k_pool" % i],
                planes["layer%d_v_pool" % i], page_table, positions,
                page_size=page_size, scale=m.attention_multiplier, name=name)
            return att, [k_out, v_out]
        return attend

    table = _table(m)
    x = _embed(ids, m, table)
    planes_out = []
    for i, kind in enumerate(m.layer_types):
        carried = None if kind == ATTENTION else (
            planes["layer%d_ssm_state" % i], planes["layer%d_conv_tail" % i],
            slot)
        x, extras = _block(x, m, i, None, paged(i), carried)
        planes_out.extend(extras)
    logits = _head(x, m, table)
    next_ids = sym.argmax(logits, axis=-1, name="next_ids")
    return sym.Group([logits] + planes_out + [next_ids])
