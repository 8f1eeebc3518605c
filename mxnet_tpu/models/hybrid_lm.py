"""Hybrid decoder-only LM: layers that carry a small per-lane state
(Mamba-2 state-space layers, or gated short convolutions) with a few
grouped-query attention layers between them, and a dense or a routed-expert
feed-forward (IBM Granite 4.0-H, ``granitemoehybrid`` with no experts:
state-space layers, no positional encoding, dense; LiquidAI LFM2-MoE,
``lfm2_moe``: short convolutions, rotary attention with per-head QK-norm,
leading dense layers and then routed experts).

ONE description of the model (:class:`HybridLM`: the layer pattern and the
sizes) and three graphs assembled from one block, as ``transformer.py``
assembles its own:

* :func:`get_hybrid_lm`: a whole sequence, ``SoftmaxOutput`` over the
  vocabulary at every position (scoring);
* :func:`get_hybrid_lm_prefill`: a prompt bucket with the prompts' true
  lengths; beside the logits it returns what every layer carries into
  decode, in :meth:`HybridLM.planes`' order: an attention layer its K and
  V ``(b, L, kv_heads * head_dim)`` (a token one row, as the planes hold it:
  whole tiles in any dtype, so a page is one contiguous piece that the
  paged-decode kernel reads where it lies, ops/paged.py), a state-space
  layer its recurrent state ``(b, heads, head_dim, state)`` and convolution
  tail ``(b, K-1, conv_dim)`` as they stand after each prompt's LAST REAL
  token;
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

The other kinds of the same block (every one off unless the description
turns it on, and a description that turns none on emits the graphs it
always did, byte for byte: tests/test_hybrid_lm.py pins them):

* mixer ``conv``, the gated short convolution: ``[B | C | X] = W_in_proj
  h``; ``c = conv1d(B * X)`` causal, depthwise, width ``conv_kernel``, no
  bias, no activation; ``W_out_proj (C * c)``.  It carries the last
  ``conv_kernel - 1`` rows of ``B * X`` a lane: a slot plane like Mamba's
  convolution tail, with no recurrent state beside it;
* attention with ``qk_norm`` (RMSNorm over each head of q and k, gains
  ``(head_dim,)``) and ``rotary_theta`` (q and k rotated by their position
  after the norm and before K is written: the pages hold rotated keys);
* feed-forward ``experts`` from layer ``num_dense_layers`` on (ops/moe.py):
  sigmoid router with a selection bias (``router_score="softmax"``, IBM
  Granite 4.0-H's expert models: the top k of the logits weighted by the
  softmax over the picked ones, no bias), ``experts_per_token`` picks a row,
  the experts ``first_expert .. first_expert + experts_held - 1`` computed
  here.  Rows that are not live (a padded lane, a position past a prompt's
  length) pick nothing.  The lane graph then returns, after ``next_ids``,
  ``expert_load`` (expert layers, num_experts) int32: the live lanes' picks
  by expert in this step (:attr:`HybridLM.lane_extras` says so).

* mixer ``latent``, latent attention (ops/paged.py; openPangu-Ultra-MoE,
  ``pangu_ultra_moe``): ``c_q = RMSNorm(W_qa h)`` (``q_rank``), ``[q_n |
  q_r] = W_qb c_q`` a head (``nope_dim | rope_dim``), ``[c | k_r] = W_kva h``
  (``kv_rank | rope_dim``), ``c = RMSNorm(c)``, ``q_r`` and the ONE ``k_r``
  rotated, ``[k_n | v] = W_kvb c`` a head (``nope_dim | v_dim``), scores
  ``(q_n . k_n + q_r . k_r) * attention_multiplier``.  It carries ONE paged
  plane a layer, ``layer<i>_latent_pool``, a token's entry the ``kv_rank +
  rope_dim`` values ``[c | k_r]`` after the norm and the rotation (and zeros
  up to whole lane tiles where the row spans more than one:
  :meth:`HybridLM.latent_row`); the sequence graphs run it expanded, the
  lane graph absorbed over that plane;
* ``shared_expert_width``: an expert layer adds ``Shared(x)``, a SiLU-gated
  MLP every row takes (the dense layers' three ops under the names
  ``layer<i>_shared_in`` / ``_gate`` / ``_out``), to its routed part;
  ``router_bias=False``: the top k of the scores alone;
* ``sandwich_norm``: ``a = x + RMSNorm(Mixer(RMSNorm(x; norm1));
  post_norm1)`` and likewise ``post_norm2`` after the feed-forward;
* ``tied_head=False``: the logits go through ``lm_head_weight`` (vocab,
  hidden), not the embedding.

* mixer ``window``, sliding-window attention (ops/paged.py; poolside
  Laguna, ``laguna``): token ``t`` attends to ``t - window < u <= t``, over
  ``window_heads`` query heads (None: ``num_heads``; the K/V heads are the
  ``attention`` kind's) rotated by plain angles of ``window_rotary_theta``
  over all of a head (None: no positions).  It carries a RING a lane and no
  pages: two slot planes ``layer<i>_k_ring`` / ``layer<i>_v_ring`` ``(window,
  kv_heads * head_dim)`` in the weights' dtype, token ``t`` at ``t %
  window``, taken and given back with the lane's first pages as a recurrent
  state is; the prefill graph returns each prompt's rings as they stand
  after its last real token;
* the ``attention`` kind's rotation may be partial (``rotary_dim``: the
  first so many features of a head, the rest pass) and scaled
  (``rotary_scaling``: YaRN's table and the factor on cosine and sine,
  ``ops.moe.rotary_table``; keys ``factor``, ``original_max``,
  ``beta_fast``, ``beta_slow``, ``attention_factor``);
* ``attn_gate``: ONE gate a query head, ``o_i <- sigmoid(W_g h)_i o_i``
  before ``W_o`` (``W_g`` ``layer<i>_gate_weight`` (heads, hidden), ``h`` the
  layer's normed input), in the ``attention`` and ``window`` kinds: the
  nodes ``layer<i>_attn_gate`` (a ``FullyConnected``), ``_attn_gate_sigmoid``
  and ``_attn_gate_mul`` (a broadcast multiply).

A description whose layers carry no slot plane (all ``attention`` or
``latent``) has no ``state_slot``: its lane graph knows a padded lane by its
page table, whose first page is the scratch page 0.

There is no windowed (catch-up / verify) graph: a recurrent state cannot be
rewound or rebuilt from cached pages, and the engine refuses what would
need one by name (generation/engine.py).
"""
import numpy as np

from .. import symbol as sym
from ..ops.moe import SCORES as _ROUTER_SCORES

MAMBA, ATTENTION, CONV, LATENT = "mamba", "attention", "conv", "latent"
WINDOW = "window"
# the kinds whose layers page what they cache (the rest hold a slot a lane)
_PAGED = (ATTENTION, LATENT)


class HybridLM:
    """The model's description, and the family object the generation
    engine asks (generation/engine.py, "The family seam").

    ``layer_types`` is the pattern (``"mamba"``, ``"conv"``, ``"attention"``,
    ``"window"`` or ``"latent"`` a layer; for ``latent`` ``head_dim`` is
    ``nope_dim + rope_dim``, ``kv_heads`` is ``num_heads``, and ``q_rank`` /
    ``kv_rank`` /
    ``nope_dim`` / ``rope_dim`` / ``v_dim`` are needed); ``num_heads`` / ``kv_heads`` / ``head_dim``
    the attention layers', ``rotary_theta`` (None: no positions) and
    ``qk_norm`` theirs too, as ``rotary_dim`` / ``rotary_scaling`` and
    ``attn_gate`` (module docstring); ``window`` / ``window_heads`` /
    ``window_rotary_theta`` the sliding-window layers'; ``intermediate`` the
    dense gated MLP's inner width; ``ssm_heads`` / ``ssm_head_dim`` / ``ssm_state`` / ``chunk`` the
    state-space layers' (one B/C group; needed only where there is one);
    ``conv_kernel`` the convolutions' width, Mamba's and the short one's;
    ``num_experts`` (0: every layer dense) / ``experts_per_token`` /
    ``expert_width`` / ``num_dense_layers`` / ``first_expert`` /
    ``experts_held`` (None: all) / ``norm_topk`` / ``routed_scaling`` /
    ``router_bias`` / ``router_score`` / ``shared_expert_width`` the expert
    layers'; ``sandwich_norm`` and ``tied_head`` the block's and the
    head's (module docstring); ``dtype`` the K/V planes' and the convolution tails'
    (the weights'); the recurrent state is float32.
    """

    name = "hybrid_lm"
    prefill_inputs = ("data", "length")
    _FIELDS = dict(vocab_size=None, hidden=None, layer_types=None,
                   num_heads=None, kv_heads=None, head_dim=None,
                   intermediate=None, ssm_heads=None, ssm_head_dim=None,
                   ssm_state=None, conv_kernel=4, chunk=256, eps=1e-5,
                   embedding_multiplier=1.0, residual_multiplier=1.0,
                   attention_multiplier=None, logits_scaling=1.0,
                   dtype="bfloat16", rotary_theta=None, qk_norm=False,
                   num_experts=0, experts_per_token=0, expert_width=0,
                   num_dense_layers=0, first_expert=0, experts_held=None,
                   norm_topk=True, routed_scaling=1.0, router_bias=True,
                   shared_expert_width=0, sandwich_norm=False,
                   tied_head=True, router_score="sigmoid", q_rank=None,
                   kv_rank=None, nope_dim=None, rope_dim=None, v_dim=None,
                   window=0, window_heads=None, window_rotary_theta=None,
                   rotary_dim=0, rotary_scaling=None, attn_gate=False)
    _REQUIRED = ("vocab_size", "hidden", "layer_types", "num_heads",
                 "kv_heads", "head_dim", "intermediate")
    _SSM = ("ssm_heads", "ssm_head_dim", "ssm_state")
    _LATENT = ("q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim",
               "rotary_theta")

    def __init__(self, **sizes):
        sizes.pop("family", None)
        unknown = set(sizes) - set(self._FIELDS)
        kinds = sizes.get("layer_types") or ()
        needed = self._REQUIRED + (self._SSM if MAMBA in kinds else ()) \
            + (self._LATENT if LATENT in kinds else ())
        missing = [k for k in needed if sizes.get(k) is None]
        if unknown or missing:
            raise ValueError("HybridLM: unknown %s, missing %s"
                             % (sorted(unknown), missing))
        for k, default in self._FIELDS.items():
            setattr(self, k, sizes.get(k, default))
        self.layer_types = tuple(self.layer_types)
        bad = set(self.layer_types) - {MAMBA, ATTENTION, CONV, LATENT,
                                       WINDOW}
        if bad or not set(self.layer_types) & set(_PAGED):
            raise ValueError("layer_types: every entry %r, %r, %r, %r or %r, "
                             "at least one attention or latent layer (the "
                             "engine's pages); got %s"
                             % (MAMBA, CONV, ATTENTION, WINDOW, LATENT,
                                sorted(bad)))
        if WINDOW in self.layer_types:
            if self.window_heads is None:
                self.window_heads = self.num_heads
            if self.window <= 0 or self.window_heads % self.kv_heads:
                raise ValueError("window: a window of %r tokens, %r query "
                                 "heads over %d K/V heads"
                                 % (self.window, self.window_heads,
                                    self.kv_heads))
        if self.rotary_scaling is not None:
            self.rotary_scaling = dict(self.rotary_scaling)
            want = {"factor", "original_max", "beta_fast", "beta_slow",
                    "attention_factor"}
            if set(self.rotary_scaling) != want or self.rotary_theta is None:
                raise ValueError("rotary_scaling: keys %s beside a "
                                 "rotary_theta; got %s"
                                 % (sorted(want),
                                    sorted(self.rotary_scaling)))
        if LATENT in self.layer_types and (
                self.head_dim != self.nope_dim + self.rope_dim
                or self.kv_heads != self.num_heads):
            raise ValueError("latent: head_dim is nope_dim + rope_dim and "
                             "kv_heads is num_heads; got %d, %d + %d, %d, %d"
                             % (self.head_dim, self.nope_dim, self.rope_dim,
                                self.kv_heads, self.num_heads))
        if self.attention_multiplier is None:
            self.attention_multiplier = float(self.head_dim) ** -0.5
        if MAMBA in self.layer_types:
            self.ssm_inner = self.ssm_heads * self.ssm_head_dim
            self.conv_dim = self.ssm_inner + 2 * self.ssm_state
        self.num_layers = len(self.layer_types)
        if self.num_experts:
            if self.experts_held is None:
                self.experts_held = self.num_experts - self.first_expert
            if not (0 < self.experts_per_token <= self.num_experts
                    and self.expert_width > 0 and self.first_expert >= 0
                    and 0 < self.experts_held
                    <= self.num_experts - self.first_expert
                    and 0 <= self.num_dense_layers < self.num_layers):
                raise ValueError(
                    "experts: %d a token of %d, width %d, held %d from %d, "
                    "after %d dense layers of %d"
                    % (self.experts_per_token, self.num_experts,
                       self.expert_width, self.experts_held,
                       self.first_expert, self.num_dense_layers,
                       self.num_layers))
            if self.shared_expert_width < 0:
                raise ValueError("shared_expert_width %d"
                                 % self.shared_expert_width)
            if self.router_score not in _ROUTER_SCORES or (
                    self.router_score == "softmax" and self.router_bias):
                raise ValueError("router_score: sigmoid, or softmax with "
                                 "router_bias=False; got %r, router_bias %r"
                                 % (self.router_score, self.router_bias))
        # a layer that carries a slot plane gives the lane graph its
        # ``state_slot``
        self.has_slots = not set(self.layer_types) <= set(_PAGED)
        self.expert_layers = tuple(
            i for i in range(self.num_layers)
            if self.num_experts and i >= self.num_dense_layers)
        # what the lane program returns after ``next_ids`` (the engine reads
        # these with the ids, one iteration late)
        self.lane_extras = ("expert_load",) if self.expert_layers else ()

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
                # a token is ONE row over the K/V heads (module docstring)
                out += [("layer%d_%s_pool" % (i, kv), "paged",
                         (self.kv_heads * self.head_dim,), self.dtype)
                        for kv in "kv"]
            elif kind == LATENT:
                out += [("layer%d_latent_pool" % i, "paged",
                         (self.latent_row(),), self.dtype)]
            elif kind == WINDOW:
                # the last ``window`` tokens' rows, token t at t % window
                out += [("layer%d_%s_ring" % (i, kv), "slot",
                         (self.window, self.kv_heads * self.head_dim),
                         self.dtype) for kv in "kv"]
            elif kind == MAMBA:
                out += [("layer%d_ssm_state" % i, "slot",
                         (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                         "float32"),
                        ("layer%d_conv_tail" % i, "slot",
                         (self.conv_kernel - 1, self.conv_dim), self.dtype)]
            else:
                out += [("layer%d_conv_tail" % i, "slot",
                         (self.conv_kernel - 1, self.hidden), self.dtype)]
        return out

    def expert_pairs(self, rows):
        """(row, pick) pairs ``rows`` live rows make over the expert
        layers."""
        return int(rows) * self.experts_per_token * len(self.expert_layers)

    def expert_bytes(self):
        """Bytes of one expert's weights as the program holds them."""
        return 3 * self.hidden * self.expert_width * \
            np.dtype(self.dtype).itemsize

    def latent_row(self):
        """Columns of a latent plane's row: the ``kv_rank + rope_dim`` values
        ``[c | k_r]``, and where they span more than one lane tile (128)
        without filling the last, zeros up to its end (openPangu's 512 + 64
        in 640).  A page of such rows is whole tiles and lies in one piece
        wherever the plane lives, so a kernel can fetch it from there: a
        chip's own layout of a ``(pages, 16, 576)`` plane puts the PAGES on
        the lanes (PERF.md section 6, PR 49)."""
        width = self.kv_rank + self.rope_dim
        return width if width <= 128 else -(-width // 128) * 128

    def latent_token_bytes(self):
        """Bytes of a token's values over the latent planes (0: no latent
        layer): what attention has to read of it, not the zeros beside."""
        return self.layer_types.count(LATENT) * (
            (self.kv_rank or 0) + (self.rope_dim or 0)) * \
            np.dtype(self.dtype).itemsize

    def ring_bytes(self):
        """Bytes of one lane's rings over the sliding-window layers (0:
        none): what a lane step fetches of them, whatever the lane's
        length."""
        return self.layer_types.count(WINDOW) * 2 * self.window \
            * self.kv_heads * self.head_dim * np.dtype(self.dtype).itemsize

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


def _conv_mixer(h, m, name, seq_len, carried):
    """The gated short convolution.  ``carried``: the sequence layout's
    ``length`` Symbol or None, or the lane layout's ``(tail plane,
    state_slot)``.  Returns the mixer's output rows and [tail]."""
    width = m.hidden
    bcx = _fc(h, 3 * width, name + "_in_proj")
    b, c, x = (sym.slice_axis(bcx, axis=-1, begin=j * width,
                              end=(j + 1) * width, name=name + "_" + part)
               for j, part in enumerate(("b", "c", "x")))
    u = sym.elemwise_mul(b, x, name=name + "_bx")
    weight = sym.Variable(name + "_conv_weight",
                          shape=(width, m.conv_kernel))
    plain = dict(activation="none", no_bias=True, name=name + "_conv")
    if seq_len is not None:
        more = [] if carried is None else [carried]
        u = sym.Reshape(u, shape=(-1, seq_len, width))
        u, tail = sym._contrib_CausalConv1D(
            u, weight, *more, use_length=bool(more), **plain)
        u = sym.Reshape(u, shape=(-1, width))
    else:
        tails, slot = carried
        u, tail = sym._contrib_CausalConv1DStep(u, weight, tails, slot,
                                                **plain)
    y = sym.elemwise_mul(c, u, name=name + "_cy")
    return _fc(y, m.hidden, name + "_out_proj"), [tail]


def _attention_mixer(h, m, name, seq_len, attend, positions=None,
                     kind=ATTENTION):
    """``attend(q, k, v, name) -> (att, extras)`` over ``(..., heads,
    head_dim)`` / ``(..., kv_heads, head_dim)``; ``positions`` the rows'
    (the sequence axis' or the lanes'), read where the model rotates.
    ``kind``: ``attention``, or ``window`` with its own head count and
    rotation."""
    lead = (-1,) if seq_len is None else (-1, seq_len)
    hd = m.head_dim
    num_heads = m.window_heads if kind == WINDOW else m.num_heads
    theta = m.window_rotary_theta if kind == WINDOW else m.rotary_theta
    # only what a description turns on is written into the graph
    turn = {}
    if kind == ATTENTION and m.rotary_dim:
        turn["rotary_dim"] = m.rotary_dim
    if kind == ATTENTION and m.rotary_scaling:
        turn.update(m.rotary_scaling)

    def heads(x, n):
        return sym.Reshape(x, shape=lead + (n, hd))

    q = heads(_fc(h, num_heads * hd, name + "_q"), num_heads)
    k = heads(_fc(h, m.kv_heads * hd, name + "_k"), m.kv_heads)
    v = heads(_fc(h, m.kv_heads * hd, name + "_v"), m.kv_heads)
    if m.qk_norm:
        q, k = (sym._contrib_RMSNorm(x, _vec("%s_%s_norm_gamma" % (name, w),
                                             hd),
                                     eps=m.eps, name="%s_%s_norm" % (name, w))
                for x, w in ((q, "q"), (k, "k")))
    if theta is not None:
        q, k = (sym._contrib_Rotary(x, positions, theta=theta,
                                    name="%s_%s_rotary" % (name, w), **turn)
                for x, w in ((q, "q"), (k, "k")))
    att, extras = attend(q, k, v, name + "_attn")
    if m.attn_gate:
        gate = sym.Activation(
            sym.FullyConnected(
                h, sym.Variable(name + "_gate_weight",
                                shape=(num_heads, m.hidden)),
                num_hidden=num_heads, no_bias=True,
                name=name + "_attn_gate"),
            act_type="sigmoid", name=name + "_attn_gate_sigmoid")
        att = sym.broadcast_mul(
            sym.Reshape(att, shape=(-1, num_heads, hd)),
            sym.Reshape(gate, shape=(-1, num_heads, 1)),
            name=name + "_attn_gate_mul")
    att = sym.Reshape(att, shape=(-1, num_heads * hd))
    return _fc(att, m.hidden, name + "_o"), extras


def _latent_mixer(h, m, name, seq_len, attend, positions):
    """Latent attention.  ``attend(q_n, q_r, latent, w_kvb, name) -> (att,
    extras)`` over ``(..., heads, nope | rope)``, the rows' ``(..., kv_rank
    + rope)`` ``[c | k_r]`` and ``W_kvb``; ``att`` is ``(..., heads,
    v_dim)``."""
    lead = (-1,) if seq_len is None else (-1, seq_len)
    heads, nope, rope, rank = m.num_heads, m.nope_dim, m.rope_dim, m.kv_rank

    def part(x, begin, end, what):
        return sym.slice_axis(x, axis=-1, begin=begin, end=end,
                              name="%s_%s" % (name, what))

    def turn(x, what):
        return sym._contrib_Rotary(x, positions, theta=m.rotary_theta,
                                   name="%s_%s_rotary" % (name, what))

    c_q = sym._contrib_RMSNorm(
        _fc(h, m.q_rank, name + "_q_a"), _vec(name + "_q_a_norm_gamma",
                                              m.q_rank),
        eps=m.eps, name=name + "_q_a_norm")
    q = sym.Reshape(_fc(c_q, heads * (nope + rope), name + "_q_b"),
                    shape=lead + (heads, nope + rope))
    q_n, q_r = part(q, 0, nope, "q_n"), turn(part(q, nope, nope + rope,
                                                  "q_r"), "q")
    ckr = _fc(h, rank + rope, name + "_kv_a")
    c = sym._contrib_RMSNorm(
        part(ckr, 0, rank, "c"), _vec(name + "_kv_a_norm_gamma", rank),
        eps=m.eps, name=name + "_kv_a_norm")
    # ONE rotated key a row, whatever the head
    k_r = sym.Reshape(part(ckr, rank, rank + rope, "k_r"),
                      shape=lead + (1, rope))
    k_r = sym.Reshape(turn(k_r, "k"), shape=(-1, rope))
    latent = sym.Reshape(sym.Concat(c, k_r, dim=1, num_args=2,
                                    name=name + "_latent"),
                         shape=lead + (rank + rope,))
    w_kvb = sym.Variable(name + "_kv_b_weight",
                         shape=(heads * (nope + m.v_dim), rank))
    att, extras = attend(q_n, q_r, latent, w_kvb, name + "_attn")
    att = sym.Reshape(att, shape=(-1, heads * m.v_dim))
    return _fc(att, m.hidden, name + "_o"), extras


def _gated_mlp(h, m, width, name):
    """The SiLU-gated MLP ``W_out (silu(W1 h) * W3 h)``, ``[W1 | W3]`` one
    product: the dense feed-forward (``name`` ``layer<i>_mlp``) and the
    shared expert (``layer<i>_shared``)."""
    h = _fc(h, 2 * width, name + "_in")
    h = sym._contrib_SiluGate(h, name=name + "_gate")
    return _fc(h, m.hidden, name + "_out")


def _experts(h, m, name, live):
    """The routed expert layer over rows ``h``; ``live`` (rows,) or None.
    Returns the rows and the router's load (num_experts,)."""
    bias = [_vec(name + "_router_bias", m.num_experts)] if m.router_bias \
        else []
    # only what departs from the op's defaults is written into the graph
    more = {} if m.router_bias else {"use_bias": False}
    if m.router_score != "sigmoid":
        more["score"] = m.router_score
    ids, weights, load = sym._contrib_MoERouter(
        h, sym.Variable(name + "_router_weight",
                        shape=(m.num_experts, m.hidden)),
        *(bias + ([] if live is None else [live])),
        use_live=live is not None,
        top_k=m.experts_per_token, normalize=m.norm_topk,
        scale=m.routed_scaling, name=name + "_router", **more)
    held, width = m.experts_held, m.expert_width
    rows = h
    h = sym._contrib_RoutedExperts(
        h, ids, weights,
        sym.Variable(name + "_experts_w13",
                     shape=(held, m.hidden, 2 * width)),
        sym.Variable(name + "_experts_w2", shape=(held, width, m.hidden)),
        num_experts=m.num_experts, first_expert=m.first_expert,
        name=name + "_experts")
    if m.shared_expert_width:
        h = sym.elemwise_add(
            _gated_mlp(rows, m, m.shared_expert_width, name + "_shared"), h,
            name=name + "_ff")
    return h, load


def _block(x, m, i, seq_len, attend, carried, positions=None, live=None):
    """Layer ``i`` over rows ``x`` (every position of every sequence, or
    every lane): returns the rows, what the layer carries and, of an expert
    layer, its router's load (else None)."""
    name = "layer%d" % i
    h = _norm(x, m, name + "_norm1")
    if m.layer_types[i] in (ATTENTION, WINDOW):
        h, extras = _attention_mixer(h, m, name, seq_len, attend, positions,
                                     m.layer_types[i])
    elif m.layer_types[i] == LATENT:
        h, extras = _latent_mixer(h, m, name, seq_len, attend, positions)
    elif m.layer_types[i] == CONV:
        h, extras = _conv_mixer(h, m, name, seq_len, carried)
    else:
        h, extras = _mamba_mixer(h, m, name, seq_len, carried)
    if m.sandwich_norm:
        h = _norm(h, m, name + "_post_norm1")
    x = _residual(x, h, m, name + "_res1")
    h = _norm(x, m, name + "_norm2")
    load = None
    if i in m.expert_layers:
        h, load = _experts(h, m, name, live)
    else:
        h = _gated_mlp(h, m, m.intermediate, name + "_mlp")
    if m.sandwich_norm:
        h = _norm(h, m, name + "_post_norm2")
    return _residual(x, h, m, name + "_res2"), extras, load


def _embed(ids, m, table):
    x = sym.Embedding(ids, weight=table, input_dim=m.vocab_size,
                      output_dim=m.hidden, name="tok_embed")
    x = sym._mul_scalar(x, scalar=m.embedding_multiplier, name="embed_scale")
    return sym.Reshape(x, shape=(-1, m.hidden), name="embed_rows")


def _head(x, m, table):
    """Final norm and the vocabulary projection (the embedding's table, or
    an untied head's own): float32 logits by rows."""
    x = _norm(x, m, "norm_f")
    if not m.tied_head:
        table = sym.Variable("lm_head_weight",
                             shape=(m.vocab_size, m.hidden))
    return sym._contrib_ScaledLogits(x, table, scale=1.0 / m.logits_scaling,
                                     name="lm_head")


def _table(m):
    return sym.Variable("tok_embed_weight", shape=(m.vocab_size, m.hidden))


def _sequence_graph(m, seq_len, length):
    def dense(q, k, v, name):
        def row(x, kv):  # as the planes hold a token
            return sym.Reshape(x, shape=(-1, seq_len,
                                         m.kv_heads * m.head_dim),
                               name="%s_%s_rows" % (name, kv))

        return sym._contrib_DenseAttention(
            q, k, v, causal=True, scale=m.attention_multiplier,
            name=name), [row(k, "k"), row(v, "v")]

    def window(q, k, v, name):
        more = [] if length is None else [length]
        att, k_ring, v_ring = sym._contrib_WindowAttention(
            q, k, v, *more, window=m.window, scale=m.attention_multiplier,
            use_length=bool(more), name=name)
        return att, [k_ring, v_ring]

    def latent(q_n, q_r, rows, w_kvb, name):
        zeros = m.latent_row() - (m.kv_rank + m.rope_dim)
        # the slab as the plane holds a token (``HybridLM.latent_row``)
        held = sym.Pad(rows, pad_width=(0, 0, 0, 0, 0, zeros),
                       name=name + "_rows") if zeros else rows
        return sym._contrib_LatentAttention(
            q_n, q_r, rows, w_kvb, scale=m.attention_multiplier,
            name=name), [held]

    table = _table(m)
    x = _embed(sym.Variable("data"), m, table)
    positions = live = None
    routes = bool(m.expert_layers) and length is not None
    if m.rotary_theta is not None or m.window_rotary_theta is not None \
            or routes:
        positions = sym._arange(start=0, stop=seq_len, name="positions")
    if routes:
        # a position past its prompt's length routes to no expert
        live = sym.Reshape(sym.broadcast_lesser(
            sym.Reshape(positions, shape=(1, seq_len)),
            sym.Reshape(length, shape=(-1, 1)), name="live"), shape=(-1,))
    carried = []
    for i in range(m.num_layers):
        attend = {LATENT: latent, WINDOW: window}.get(m.layer_types[i],
                                                      dense)
        x, extras, _ = _block(x, m, i, seq_len, attend, length, positions,
                              live)
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
    ``page_table`` (lanes, max_pages) (``state_slot`` only where a layer
    carries a slot plane: a state, a convolution tail, a ring) and the planes
    of :meth:`HybridLM.planes`; outputs the logits (lanes, vocab), the planes
    in that order, then ``next_ids`` (lanes,) and :attr:`HybridLM.
    lane_extras`.  ``positions`` places the attention layers' K/V and, where
    the model rotates, turns their queries and keys."""
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
        if m.layer_types[i] == LATENT:
            def attend(q_n, q_r, rows, w_kvb, name):
                att, pool = sym._contrib_PagedLatentAttention(
                    q_n, q_r, rows, w_kvb, planes["layer%d_latent_pool" % i],
                    page_table, positions, page_size=page_size,
                    scale=m.attention_multiplier, name=name)
                return att, [pool]
            return attend

        if m.layer_types[i] == WINDOW:
            def attend(q, k, v, name):
                att, k_out, v_out = sym._contrib_WindowAttentionStep(
                    q, k, v, planes["layer%d_k_ring" % i],
                    planes["layer%d_v_ring" % i], slot, positions,
                    scale=m.attention_multiplier, name=name)
                return att, [k_out, v_out]
            return attend

        def attend(q, k, v, name):
            att, k_out, v_out = sym._contrib_PagedAttention(
                q, k, v, planes["layer%d_k_pool" % i],
                planes["layer%d_v_pool" % i], page_table, positions,
                page_size=page_size, scale=m.attention_multiplier, name=name)
            return att, [k_out, v_out]
        return attend

    table = _table(m)
    x = _embed(ids, m, table)
    # a padded lane of the bucket is parked on the scratch slot (or, where
    # no layer carries a slot, on the scratch page): it routes to no expert
    live = None
    if m.expert_layers and m.has_slots:
        live = sym._greater_scalar(slot, scalar=0, name="live")
    elif m.expert_layers:
        live = sym._greater_scalar(sym.Reshape(
            sym.slice_axis(page_table, axis=1, begin=0, end=1,
                           name="first_page"), shape=(-1,)),
            scalar=0, name="live")
    planes_out, loads = [], []
    for i, kind in enumerate(m.layer_types):
        carried = {ATTENTION: None, LATENT: None, WINDOW: None,
                   CONV: (planes.get("layer%d_conv_tail" % i), slot),
                   MAMBA: (planes.get("layer%d_ssm_state" % i),
                           planes.get("layer%d_conv_tail" % i), slot)}[kind]
        x, extras, load = _block(x, m, i, None, paged(i), carried, positions,
                                 live)
        planes_out.extend(extras)
        if load is not None:
            loads.append(sym.Reshape(load, shape=(1, -1)))
    logits = _head(x, m, table)
    next_ids = sym.argmax(logits, axis=-1, name="next_ids")
    more = [sym.Concat(*loads, dim=0, num_args=len(loads),
                       name="expert_load")] if loads else []
    return sym.Group([logits] + planes_out + [next_ids] + more)
