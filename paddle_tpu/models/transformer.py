"""Transformer encoder-decoder (the second driver metric: Transformer-base
tokens/sec/chip).

Functional contract follows the reference's Transformer test model
(python/paddle/fluid/tests/unittests/transformer_model.py: multi-head
attention, position encoding, pre/post-process residual+norm+dropout,
label-smoothed softmax CE) but the design is TPU-first rather than a
translation: everything is static-shape dense [batch, seq_len] tensors, the
causal and padding masks are additive biases broadcast into the pre-softmax
logits (no LoD, no data-dependent shapes), and the whole step traces into a
single XLA program whose attention/FFN matmuls tile onto the MXU.
"""

from __future__ import annotations

import numpy as np

from .. import fluid
from ..fluid import layers
from ..fluid.initializer import NumpyArrayInitializer
from ..fluid.param_attr import ParamAttr

_NEG_INF = -1e9


class Config:
    def __init__(self, name, src_vocab_size, tgt_vocab_size, d_model,
                 d_inner, n_head, n_layer, dropout=0.1, label_smooth=0.1,
                 moe_experts=0, moe_top_k=2, moe_aux_weight=1e-2,
                 stacked=False, ring_attention=False, n_microbatches=4,
                 recompute=False):
        self.name = name
        self.src_vocab_size = src_vocab_size
        self.tgt_vocab_size = tgt_vocab_size
        self.d_model = d_model
        self.d_inner = d_inner
        self.n_head = n_head
        self.n_layer = n_layer
        self.dropout = dropout
        self.label_smooth = label_smooth
        # moe_experts > 0 replaces every FFN with an expert-parallel MoE
        # layer (Switch-style; experts shard over an "ep" mesh axis)
        self.moe_experts = moe_experts
        self.moe_top_k = moe_top_k
        self.moe_aux_weight = moe_aux_weight
        # stacked=True builds the encoder/decoder as ONE mesh-aware
        # layer-stack op with [L, ...] params (layers.transformer_*_stack):
        # pipeline-parallel over "pp", Megatron-TP over "mp", ring-
        # attention over "sp" — the pipeline-capable flagship build.
        # Residual dropout only in this mode (see transformer_stack).
        self.stacked = stacked
        # ring_attention=True keeps the per-layer graph but routes every
        # attention through layers.ring_attention, so the UNstacked model
        # sequence-parallelizes over an "sp" mesh axis too.  Attention-
        # probability dropout is skipped in this mode (the [T, T] matrix
        # never materializes under the ring).
        self.ring_attention = ring_attention
        self.n_microbatches = n_microbatches
        # recompute=True (stacked mode) wraps each layer in
        # jax.checkpoint: backward rematerializes activations layer by
        # layer — peak memory O(T*D) instead of O(L*T*D) for long
        # sequences at the cost of one extra forward
        self.recompute = recompute


def base_config():
    """Transformer-base (Vaswani et al.): d_model 512, 8 heads, 6 layers."""
    return Config("base", src_vocab_size=30000, tgt_vocab_size=30000,
                  d_model=512, d_inner=2048, n_head=8, n_layer=6)


def tiny_config():
    """CPU-test scale."""
    return Config("tiny", src_vocab_size=1000, tgt_vocab_size=1000,
                  d_model=64, d_inner=128, n_head=4, n_layer=2)


def _position_encoding(max_len, d_model):
    """Sinusoid table [max_len, d_model] (Vaswani et al. eq. 5)."""
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    div = np.exp(np.arange(0, d_model, 2, dtype=np.float64)
                 * -(np.log(10000.0) / d_model))
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return table


def _shared_causal_bias(lq, lk):
    """One additive triu causal mask per (program, shape) — every decoder
    layer shares the same constant var instead of re-materializing it."""
    from .. import fluid as _fluid

    prog = _fluid.default_main_program()
    cache = getattr(prog, "_causal_bias_cache", None)
    if cache is None:
        cache = prog._causal_bias_cache = {}
    var = cache.get((lq, lk))
    if var is None:
        causal_np = np.triu(np.full((lq, lk), _NEG_INF, np.float32), k=1)
        var = cache[(lq, lk)] = layers.assign(causal_np)
    return var


def _postprocess(prev, out, dropout):
    """Residual add + layer norm (+ dropout on the sublayer output)."""
    if dropout:
        out = layers.dropout(out, dropout_prob=dropout)
    return layers.layer_norm(layers.elementwise_add(prev, out),
                             begin_norm_axis=2)


def _multi_head_attention(q_in, k_in, v_in, bias, d_model, n_head,
                          dropout, prefix, causal=False, use_ring=False):
    """[b, lq, d] x [b, lk, d] -> [b, lq, d]; bias broadcasts into the
    [b, h, lq, lk] logits (None, [lq, lk] causal, or [b, 1, 1, lk] padding).

    use_ring=True routes the attention through layers.ring_attention
    (sequence-parallel over an "sp" mesh axis, mathematically identical
    single-device); the causal mask is then expressed via the op's
    ``causal`` flag and ``bias`` must be a key-position padding bias
    ([b, 1, 1, lk]) or None — and attention-probability dropout is skipped
    (the ring never materializes the probability matrix).

    The PROGRAM depends on the flash gate at build time (ROADMAP D20):
    where ``kernel_choice.gate("flash")`` is open when this is called (a
    TPU backend, or ``PADDLE_TPU_FLASH=1``) the same fused op is emitted as
    for ``use_ring``, and it runs the Pallas kernel or, the gate closed
    again at run time, its XLA twin; where it is closed (a build on the
    CPU) the composition ``matmul``, ``softmax``, ``dropout``, ``matmul``
    is.  Only the composition applies attention-probability dropout, so a
    build on the chip trains without it at any ``dropout``."""
    lq, lk = q_in.shape[1], k_in.shape[1]
    d_k = d_model // n_head
    q = layers.fc(q_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name=f"{prefix}_q_w"))
    k = layers.fc(k_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name=f"{prefix}_k_w"))
    v = layers.fc(v_in, d_model, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name=f"{prefix}_v_w"))
    # [b, l, d] -> [b, h, l, d_k]
    q = layers.transpose(layers.reshape(q, [-1, lq, n_head, d_k]),
                         perm=[0, 2, 1, 3])
    k = layers.transpose(layers.reshape(k, [-1, lk, n_head, d_k]),
                         perm=[0, 2, 1, 3])
    v = layers.transpose(layers.reshape(v, [-1, lk, n_head, d_k]),
                         perm=[0, 2, 1, 3])
    from ..ops import kernel_choice
    if use_ring or kernel_choice.gate("flash"):
        # the fused attention op: ring (sp mesh axis) / Pallas flash / XLA
        # full softmax, chosen where it is lowered; prob-dropout is skipped
        ctx = layers.ring_attention(q, k, v, causal=causal,
                                    scale=d_k ** -0.5, bias=bias)
    else:
        logits = layers.matmul(layers.scale(q, scale=d_k ** -0.5), k,
                               transpose_y=True)
        if causal:
            # one shared [lq, lk] mask var per program+shape: layers would
            # otherwise each carry their own identical triu constant
            logits = layers.elementwise_add(logits,
                                            _shared_causal_bias(lq, lk))
        if bias is not None:
            logits = layers.elementwise_add(logits, bias)
        weights = layers.softmax(logits)
        if dropout:
            weights = layers.dropout(weights, dropout_prob=dropout)
        ctx = layers.matmul(weights, v)                  # [b, h, lq, d_k]
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         [-1, lq, d_model])
    return layers.fc(ctx, d_model, num_flatten_dims=2, bias_attr=False,
                     param_attr=ParamAttr(name=f"{prefix}_o_w"))


def _ffn(x, d_inner, d_model, prefix, cfg=None, aux_losses=None):
    if cfg is not None and cfg.moe_experts:
        out, aux = layers.moe_ffn(x, num_experts=cfg.moe_experts,
                                  hidden_size=d_inner,
                                  top_k=cfg.moe_top_k)
        if aux_losses is not None:
            aux_losses.append(aux)
        return out
    h = layers.fc(x, d_inner, num_flatten_dims=2, act="relu",
                  param_attr=ParamAttr(name=f"{prefix}_ffn1_w"))
    return layers.fc(h, d_model, num_flatten_dims=2,
                     param_attr=ParamAttr(name=f"{prefix}_ffn2_w"))


def _embed(word, vocab_size, seq_len, cfg, name):
    emb = layers.embedding(
        word, size=[vocab_size, cfg.d_model],
        param_attr=ParamAttr(
            name=f"{name}_emb",
            initializer=fluid.initializer.NormalInitializer(
                0.0, cfg.d_model ** -0.5)))
    emb = layers.scale(emb, scale=cfg.d_model ** 0.5)
    pos = layers.create_parameter(
        shape=[seq_len, cfg.d_model], dtype="float32",
        attr=ParamAttr(name=f"{name}_pos_enc",
                       initializer=NumpyArrayInitializer(
                           _position_encoding(seq_len, cfg.d_model)),
                       trainable=False))
    out = layers.elementwise_add(emb, pos)
    if cfg.dropout:
        out = layers.dropout(out, dropout_prob=cfg.dropout)
    return out


def _padding_bias(word, seq_len):
    """[b, len] int ids -> additive bias [b, 1, 1, len]: NEG_INF at pad(0)."""
    zeros = layers.fill_constant_batch_size_like(
        word, shape=[-1, seq_len], dtype="int64", value=0)
    is_pad = layers.cast(layers.equal(word, zeros), "float32")
    bias = layers.scale(is_pad, scale=_NEG_INF)
    return layers.reshape(bias, [-1, 1, 1, seq_len])


def moe_config():
    """Switch-Transformer-style MoE variant of the tiny config (expert
    parallelism demo/test model; SURVEY.md §2.6: MoE/EP beyond-reference)."""
    c = tiny_config()
    c.name = "moe_tiny"
    c.moe_experts = 4
    return c


def encoder(src_word, cfg, src_len, aux_losses=None):
    """Named for the device trace (``fluid.name_scope``): ``embed``, then
    ``encoder.layer<i>.attention`` / ``.ffn``, each with its residual add
    and norm."""
    with fluid.name_scope("embed"):
        enc = _embed(src_word, cfg.src_vocab_size, src_len, cfg, "src")
        src_bias = _padding_bias(src_word, src_len)
    if cfg.stacked:
        with fluid.name_scope("encoder"):
            enc = layers.transformer_encoder_stack(
                enc, bias=src_bias, n_layer=cfg.n_layer, n_head=cfg.n_head,
                d_inner=cfg.d_inner, dropout=cfg.dropout,
                n_microbatches=cfg.n_microbatches,
                recompute=getattr(cfg, "recompute", False))
        return enc, src_bias
    for i in range(cfg.n_layer):
        with fluid.name_scope(f"encoder.layer{i}.attention"):
            attn = _multi_head_attention(
                enc, enc, enc, src_bias, cfg.d_model, cfg.n_head,
                cfg.dropout, prefix=f"enc{i}_self",
                use_ring=cfg.ring_attention)
            enc = _postprocess(enc, attn, cfg.dropout)
        with fluid.name_scope(f"encoder.layer{i}.ffn"):
            ff = _ffn(enc, cfg.d_inner, cfg.d_model, prefix=f"enc{i}",
                      cfg=cfg, aux_losses=aux_losses)
            enc = _postprocess(enc, ff, cfg.dropout)
    return enc, src_bias


def _head(dec, cfg):
    with fluid.name_scope("head"):
        return layers.fc(dec, cfg.tgt_vocab_size, num_flatten_dims=2,
                         param_attr=ParamAttr(name="out_proj_w"))


def decoder(tgt_word, enc_out, src_bias, cfg, tgt_len, aux_losses=None):
    """``embed``, ``decoder.layer<i>.self_attention`` / ``.cross_attention``
    / ``.ffn``, and the projection to the vocabulary under ``head``."""
    with fluid.name_scope("embed"):
        dec = _embed(tgt_word, cfg.tgt_vocab_size, tgt_len, cfg, "tgt")
    if cfg.stacked:
        with fluid.name_scope("decoder"):
            dec = layers.transformer_decoder_stack(
                dec, enc_out, src_bias=src_bias, n_layer=cfg.n_layer,
                n_head=cfg.n_head, d_inner=cfg.d_inner, dropout=cfg.dropout,
                n_microbatches=cfg.n_microbatches,
                recompute=getattr(cfg, "recompute", False))
        return _head(dec, cfg)
    for i in range(cfg.n_layer):
        with fluid.name_scope(f"decoder.layer{i}.self_attention"):
            self_attn = _multi_head_attention(
                dec, dec, dec, None, cfg.d_model, cfg.n_head, cfg.dropout,
                prefix=f"dec{i}_self", causal=True,
                use_ring=cfg.ring_attention)
            dec = _postprocess(dec, self_attn, cfg.dropout)
        with fluid.name_scope(f"decoder.layer{i}.cross_attention"):
            cross = _multi_head_attention(
                dec, enc_out, enc_out, src_bias, cfg.d_model, cfg.n_head,
                cfg.dropout, prefix=f"dec{i}_cross",
                use_ring=cfg.ring_attention)
            dec = _postprocess(dec, cross, cfg.dropout)
        with fluid.name_scope(f"decoder.layer{i}.ffn"):
            ff = _ffn(dec, cfg.d_inner, cfg.d_model, prefix=f"dec{i}",
                      cfg=cfg, aux_losses=aux_losses)
            dec = _postprocess(dec, ff, cfg.dropout)
    return _head(dec, cfg)


def forward(cfg, src_len, tgt_len):
    """Build data layers + logits + label-smoothed CE loss.  Returns
    (src_word, tgt_word, lbl_word, avg_cost, logits)."""
    src_word = layers.data(name="src_word", shape=[src_len], dtype="int64")
    tgt_word = layers.data(name="tgt_word", shape=[tgt_len], dtype="int64")
    lbl_word = layers.data(name="lbl_word", shape=[tgt_len, 1], dtype="int64")

    aux_losses = []
    enc_out, src_bias = encoder(src_word, cfg, src_len, aux_losses)
    logits = decoder(tgt_word, enc_out, src_bias, cfg, tgt_len, aux_losses)

    with fluid.name_scope("head"):
        if cfg.label_smooth:
            hot = layers.one_hot(lbl_word, cfg.tgt_vocab_size)
            smooth = layers.label_smooth(hot, epsilon=cfg.label_smooth)
            cost = layers.softmax_with_cross_entropy(logits, smooth,
                                                     soft_label=True)
        else:
            cost = layers.softmax_with_cross_entropy(logits, lbl_word)
        # mask loss at pad targets so padding doesn't dilute the objective
        zeros = layers.fill_constant_batch_size_like(
            lbl_word, shape=[-1, tgt_len, 1], dtype="int64", value=0)
        non_pad = layers.cast(
            layers.logical_not(layers.equal(lbl_word, zeros)), "float32")
        cost = layers.elementwise_mul(cost, non_pad)
        avg_cost = layers.elementwise_div(
            layers.reduce_sum(cost),
            layers.elementwise_add(layers.reduce_sum(non_pad),
                                   layers.fill_constant([1], "float32", 1e-8)))
        for aux in aux_losses:  # Switch load-balancing losses (MoE configs)
            avg_cost = layers.elementwise_add(
                avg_cost, layers.scale(aux, scale=cfg.moe_aux_weight))
    return src_word, tgt_word, lbl_word, avg_cost, logits


# ---------------------------------------------------------------------------
# Step-form decode (ISSUE 15): slot-based KV cache, one compiled decode step
# ---------------------------------------------------------------------------


def decode_lm_config():
    """Decoder-only LM at CPU-test scale for the continuous-batching
    serving path (``serving.decode.DecodeEngine``): self-attention only,
    single head, no dropout — the deterministic greedy-decode model the
    convoy/bitwise oracles run against."""
    return Config("decode_lm", src_vocab_size=64, tgt_vocab_size=64,
                  d_model=16, d_inner=32, n_head=1, n_layer=2,
                  dropout=0.0, label_smooth=0.0)


class DecodeModel:
    """Step-form decoder-only transformer LM: the decode programs the
    continuous-batching engine drives token-by-token.

    Three program families, all sharing parameters AND per-layer KV
    caches BY NAME through one scope:

     - ``startup``: initializes every weight plus the per-layer
       ``dlm{i}_cache_k/v`` caches — persistable ``[max_slots, max_len,
       d_model]`` zeros that live device-resident across dispatches (the
       slot-based KV cache);
     - ``step_program``: ONE fixed-shape program over ALL slots — embed
       the last token, project q/k/v, ``kv_cache_update`` this tick's
       K/V at each slot's write position, attend over the cache row
       under a host-fed ``-inf`` validity bias, project logits,
       ``token_select`` greedily.  Fixed ``[max_slots, ...]`` shapes ⇒
       exactly one executable regardless of which slots are live;
     - ``prefill_program(plen)``: one program per prompt-length bucket
       (single request): local causal attention over the prompt window
       and a ``kv_cache_update`` scatter of the whole K/V prefix into
       the request's slot at position 0.  No logits — the first decode
       tick re-derives position ``len-1`` (same weights, same token ⇒
       bit-identical K/V) and emits the first token, so the executable
       set stays small.

    Bitwise sequential-equivalence contract: every op is row-independent
    over the slot dim and masked cache positions contribute EXACTLY zero
    (the validity bias is ``-inf``, so softmax weights vanish in IEEE
    rather than shrinking to ~e-30), so a stream's tokens are a function
    of its own prompt alone — continuous batching cannot perturb them.

    All programs set ``_donate_state`` so the executor donates the cache
    buffers and XLA aliases them window-over-window (PR 6 machinery)."""

    # decode-step feed names (the engine builds these arrays per tick)
    DC_TOKENS, DC_POSENC, DC_BIAS, DC_POS, DC_ACTIVE = (
        "dc_tokens", "dc_posenc", "dc_bias", "dc_pos", "dc_active")
    # paged-mode decode feeds (ISSUE 19): the slot->page indirection and
    # this tick's per-slot write destination (trash page when inactive
    # or stalled)
    DC_PTABLE, DC_WPAGE, DC_WOFF = "dc_ptable", "dc_wpage", "dc_woff"
    # prefill feed names (per admitted request)
    PF_TOKENS, PF_SLOT = "pf_tokens", "pf_slot"
    # paged-mode prefill feed: one page id per prompt page of the bucket
    # (trash for bucket pad pages)
    PF_PAGES = "pf_pages"
    # speculative-verify feed names (ISSUE 20): the k+1-position verify
    # step serving/specdec dispatches once per spec tick.  Per-position
    # feeds are indexed — ``SP_TOK.format(j)`` for j in 0..k — because
    # the program is built as k+1 shape-clones of the step body.
    SP_TOK, SP_PE, SP_BIAS_J = "sp_tok{}", "sp_pe{}", "sp_bias{}"
    # per-position K/V write destinations [S]: dense = (slot |
    # max_slots-OOB trash, absolute position), paged = (page | trash
    # page, in-page offset)
    SP_WROW, SP_WOFF = "sp_wrow{}", "sp_woff{}"
    SP_DRAFT, SP_ACTIVE, SP_PTABLE = "sp_draft", "sp_active", "sp_ptable"

    def __init__(self, cfg=None, max_slots=None, max_len=None,
                 prefill_buckets=None, end_id=1, seed=7, paged=None,
                 page_size=None, num_pages=None):
        from ..fluid import envcontract as _ec

        self.cfg = cfg or decode_lm_config()
        if self.cfg.dropout:
            raise ValueError("decode models must be deterministic: "
                             "build the config with dropout=0")
        self.max_slots = int(max_slots if max_slots is not None
                             else _ec.get("PADDLE_SERVE_SLOTS"))
        self.max_len = int(max_len if max_len is not None
                           else _ec.get("PADDLE_SERVE_MAX_LEN"))
        if prefill_buckets is None:
            raw = _ec.get("PADDLE_SERVE_PREFILL_BUCKETS") or ""
            prefill_buckets = [int(b) for b in str(raw).split(",") if b]
        self.prefill_buckets = sorted(
            {int(b) for b in prefill_buckets if int(b) <= self.max_len})
        if not self.prefill_buckets:
            raise ValueError(
                f"no viable prefill bucket <= max_len ({self.max_len})")
        # paged KV cache (ISSUE 19): same program families, but the
        # per-layer caches become [num_pages + 1, page_size, d_model]
        # page pools (row num_pages = trash) addressed through per-tick
        # page-table feeds.  Feed shapes stay fixed, so the closed
        # executable set survives: still 1 step + one per bucket.
        self.paged = bool(_ec.get("PADDLE_SERVE_PAGED")) if paged is None \
            else bool(paged)
        if self.paged:
            self.page_size = int(page_size if page_size is not None
                                 else _ec.get("PADDLE_SERVE_PAGE_SIZE"))
            if self.page_size < 1 or self.max_len % self.page_size:
                raise ValueError(
                    f"page_size ({self.page_size}) must divide max_len "
                    f"({self.max_len})")
            bad = [b for b in self.prefill_buckets
                   if b % self.page_size]
            if bad:
                raise ValueError(
                    f"page_size ({self.page_size}) must divide every "
                    f"prefill bucket; {bad} are not divisible")
            self.pages_per_slot = self.max_len // self.page_size
            np_req = int(num_pages if num_pages is not None
                         else _ec.get("PADDLE_SERVE_NUM_PAGES"))
            # 0 = auto: dense-equal capacity (every slot can run to
            # max_len); smaller pools oversubscribe and rely on the
            # engine's admission backpressure + growth stalls
            self.num_pages = np_req or self.max_slots * self.pages_per_slot
            if self.num_pages < self.pages_per_slot:
                raise ValueError(
                    f"num_pages ({self.num_pages}) cannot hold even one "
                    f"full slot ({self.pages_per_slot} pages)")
            self.trash_page = self.num_pages
        else:
            self.page_size = self.num_pages = self.trash_page = None
            self.pages_per_slot = None
        self.end_id = int(end_id)
        self.seed = int(seed)
        self.vocab_size = int(self.cfg.tgt_vocab_size)
        self.pos_table = _position_encoding(self.max_len, self.cfg.d_model)
        self.startup = fluid.Program()
        self._prefill = {}
        self._spec = {}
        self.step_program, self.step_fetch, self.logits_fetch = \
            self._build_step()

    # -- graph pieces shared by the step and prefill programs --

    def _cache_var(self, name):
        """The persistable cache param (zero-init, frozen): the dense
        [S, L, D] slot cache, or in paged mode the [P + 1, ps, D] page
        pool whose last row is the trash page.  Names keep the
        ``_cache_`` marker either way — the scrub/rebind machinery and
        ``weight_names`` key on it."""
        from ..fluid.initializer import ConstantInitializer
        from ..fluid.layers import tensor as _tensor

        shape = ([self.num_pages + 1, self.page_size, self.cfg.d_model]
                 if self.paged
                 else [self.max_slots, self.max_len, self.cfg.d_model])
        return _tensor.create_parameter(
            shape=shape, dtype="float32",
            attr=ParamAttr(name=name, trainable=False,
                           initializer=ConstantInitializer(0.0)))

    def _layer(self, x, i, attn):
        """One decoder layer over x [n, t, D]; ``attn(q, k, v)`` supplies
        the cache-backed (step) or windowed-causal (prefill) attention."""
        d, f = self.cfg.d_model, self.cfg.d_inner
        proj = dict(num_flatten_dims=2, bias_attr=False)
        q = layers.fc(x, d, param_attr=ParamAttr(name=f"dlm{i}_q_w"), **proj)
        k = layers.fc(x, d, param_attr=ParamAttr(name=f"dlm{i}_k_w"), **proj)
        v = layers.fc(x, d, param_attr=ParamAttr(name=f"dlm{i}_v_w"), **proj)
        ctx = attn(q, k, v)
        o = layers.fc(ctx, d, param_attr=ParamAttr(name=f"dlm{i}_o_w"),
                      **proj)
        x = layers.layer_norm(
            layers.elementwise_add(x, o), begin_norm_axis=2,
            param_attr=ParamAttr(name=f"dlm{i}_ln1_s"),
            bias_attr=ParamAttr(name=f"dlm{i}_ln1_b"))
        h = layers.fc(x, f, act="relu",
                      param_attr=ParamAttr(name=f"dlm{i}_ffn1_w"), **proj)
        ff = layers.fc(h, d, param_attr=ParamAttr(name=f"dlm{i}_ffn2_w"),
                       **proj)
        return layers.layer_norm(
            layers.elementwise_add(x, ff), begin_norm_axis=2,
            param_attr=ParamAttr(name=f"dlm{i}_ln2_s"),
            bias_attr=ParamAttr(name=f"dlm{i}_ln2_b"))

    def _embed(self, tokens, posenc_var):
        emb = layers.embedding(tokens, size=[self.vocab_size,
                                             self.cfg.d_model],
                               param_attr=ParamAttr(name="dlm_emb"))
        return layers.elementwise_add(
            layers.scale(emb, scale=self.cfg.d_model ** 0.5), posenc_var,
            axis=emb.shape and len(emb.shape) - len(posenc_var.shape))

    # -- the one compiled decode step --

    def _build_step(self):
        s, l = self.max_slots, self.max_len
        d, v = self.cfg.d_model, self.vocab_size
        prog = fluid.Program()
        prog.random_seed = self.startup.random_seed = self.seed
        prog._donate_state = True  # single engine worker owns dispatch
        with fluid.program_guard(prog, self.startup), \
                fluid.unique_name.guard():
            tokens = layers.data(self.DC_TOKENS, shape=[s, 1],
                                 dtype="int64", append_batch_size=False)
            posenc = layers.data(self.DC_POSENC, shape=[s, d],
                                 dtype="float32", append_batch_size=False)
            bias = layers.data(self.DC_BIAS, shape=[s, 1, l],
                               dtype="float32", append_batch_size=False)
            pos = layers.data(self.DC_POS, shape=[s], dtype="int64",
                              append_batch_size=False)
            active = layers.data(self.DC_ACTIVE, shape=[s],
                                 dtype="float32", append_batch_size=False)
            slots = layers.assign(np.arange(s, dtype=np.int64))
            if self.paged:
                # slot->page indirection, fed fresh each tick.  Gathered
                # length pages_per_slot * page_size == max_len, so the
                # SAME [S, 1, L] validity bias masks trash/stale pages
                # with exact -inf — bitwise equality with the dense step
                # rides on that.
                ptable = layers.data(
                    self.DC_PTABLE, shape=[s, self.pages_per_slot],
                    dtype="int64", append_batch_size=False)
                wpage = layers.data(self.DC_WPAGE, shape=[s],
                                    dtype="int64", append_batch_size=False)
                woff = layers.data(self.DC_WOFF, shape=[s],
                                   dtype="int64", append_batch_size=False)

            x = layers.reshape(self._embed(tokens, posenc), [s, 1, d])

            def cache_attn(q, k, v_, i):
                ck = self._cache_var(f"dlm{i}_cache_k")
                cv = self._cache_var(f"dlm{i}_cache_v")
                # write BEFORE reading so position `pos` (this token)
                # participates in its own attention window
                if self.paged:
                    # same scatter op, page-pool addressed: row = page,
                    # offset = position within the page (inactive and
                    # stalled slots aim at the trash page)
                    ck = layers.kv_cache_update(ck, k, wpage, woff)
                    cv = layers.kv_cache_update(cv, v_, wpage, woff)
                    return layers.paged_attention(
                        layers.scale(q, scale=d ** -0.5), ck, cv,
                        ptable, bias, scale=1.0)             # [S, 1, D]
                ck = layers.kv_cache_update(ck, k, slots, pos)
                cv = layers.kv_cache_update(cv, v_, slots, pos)
                scores = layers.matmul(
                    layers.scale(q, scale=d ** -0.5), ck,
                    transpose_y=True)                        # [S, 1, L]
                probs = layers.softmax(
                    layers.elementwise_add(scores, bias))
                return layers.matmul(probs, cv)              # [S, 1, D]

            for i in range(self.cfg.n_layer):
                x = self._layer(x, i,
                                lambda q, k, v_, i=i: cache_attn(q, k, v_, i))
            logits = layers.fc(layers.reshape(x, [s, d]), v,
                               bias_attr=False,
                               param_attr=ParamAttr(name="dlm_out_w"))
            nxt = layers.token_select(logits, mask=active,
                                      end_id=self.end_id)
        return prog, nxt.name, logits.name

    # -- bucketed prefill --

    def bucket_for(self, prompt_len):
        """Smallest prefill bucket holding ``prompt_len`` (None = none)."""
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        return None

    def prefill_program(self, plen):
        """The (lazily built, cached) prefill program for bucket ``plen``:
        one request, prompt padded to ``plen``, K/V prefix scattered into
        the fed slot at position 0.  Weights come from the step
        program's startup — this builder's throwaway startup is never
        run."""
        prog = self._prefill.get(plen)
        if prog is not None:
            return prog
        if plen not in self.prefill_buckets:
            raise ValueError(f"{plen} is not a prefill bucket "
                             f"({self.prefill_buckets})")
        d = self.cfg.d_model
        prog, scratch_startup = fluid.Program(), fluid.Program()
        prog.random_seed = scratch_startup.random_seed = self.seed
        prog._donate_state = True
        with fluid.program_guard(prog, scratch_startup), \
                fluid.unique_name.guard():
            tokens = layers.data(self.PF_TOKENS, shape=[1, plen],
                                 dtype="int64", append_batch_size=False)
            if self.paged:
                # per-page destinations instead of a slot id: the K/V
                # window is cut into bucket//page_size page-sized chunks
                # and scattered to wherever the pool placed them (pad
                # pages beyond the prompt are fed the trash page)
                n_pp = plen // self.page_size
                pages = layers.data(self.PF_PAGES, shape=[n_pp],
                                    dtype="int64", append_batch_size=False)
                zeros = layers.fill_constant([n_pp], "int64", 0)
            else:
                slot = layers.data(self.PF_SLOT, shape=[1], dtype="int64",
                                   append_batch_size=False)
                start = layers.fill_constant([1], "int64", 0)
            posenc = layers.assign(self.pos_table[:plen])     # [p, D]
            x = self._embed(tokens, posenc)                   # [1, p, D]

            def window_attn(q, k, v_, i):
                ck = self._cache_var(f"dlm{i}_cache_k")
                cv = self._cache_var(f"dlm{i}_cache_v")
                if self.paged:
                    kr = layers.reshape(k, [n_pp, self.page_size, d])
                    vr = layers.reshape(v_, [n_pp, self.page_size, d])
                    layers.kv_cache_update(ck, kr, pages, zeros)
                    layers.kv_cache_update(cv, vr, pages, zeros)
                else:
                    layers.kv_cache_update(ck, k, slot, start)
                    layers.kv_cache_update(cv, v_, slot, start)
                # the prompt window attends within itself (causal); the
                # cache is write-only here — decode ticks read it
                scores = layers.matmul(
                    layers.scale(q, scale=d ** -0.5), k,
                    transpose_y=True)                        # [1, p, p]
                scores = layers.elementwise_add(
                    scores, _shared_causal_bias(plen, plen), axis=1)
                return layers.matmul(layers.softmax(scores), v_)

            for i in range(self.cfg.n_layer):
                x = self._layer(x, i,
                                lambda q, k, v_, i=i: window_attn(q, k, v_, i))
        self._prefill[plen] = prog
        return prog

    # -- speculative verify (ISSUE 20) --

    def spec_program(self, k):
        """The (lazily built, cached) verify program for speculation
        depth ``k``: ONE fixed-shape dispatch scoring k + 1 positions
        per slot.  Position j's sub-graph is a SHAPE-CLONE of the step
        program's body — embed [S, 1] tokens, project q/k/v, write this
        position's K/V, attend under a [S, 1, L] validity bias, project
        [S, V] logits — repeated k + 1 times over a shared cache (writes
        land in program order, so position j attends over positions
        <= pos + j exactly as sequential decode would).  The k + 1
        logits rows stack into [S, k+1, V] and ``spec_accept`` takes the
        longest draft == argmax prefix plus the correction token.

        Why clones instead of one wide [S, k+1, ·] step: XLA's fusion
        choices change with the position width (the matmul+bias+softmax
        epilogue reassociates), so a wide verify's logits drift ~1e-7
        from the step's — enough to flip an argmax at a near-tie.  With
        same-shaped sub-graphs the compiler has the step program's exact
        fusion problem, so verify logits at position j are bitwise the
        step's at that position; greedy acceptance is then bitwise
        sequential BY CONSTRUCTION, not by tie-luck.  The whole point of
        the verify step is fewer host round-trips and one dispatch per
        tick, which survives; the tests/test_specdec.py bitwise oracles
        enforce this contract.

        The only write-path difference from the step: K/V lands through
        ``kv_cache_scatter`` at explicit fed (row, offset) pairs, so
        non-participating slots steer to the dense out-of-bounds trash
        slot / the paged trash page instead of writing garbage at a
        clamped position.

        Returns ``(prog, tokens_fetch, naccept_fetch, logits_fetch)``;
        the logits fetch is position 0's [S, V] — exactly the plain
        step's logits, so the engine's tick monitor keeps watching the
        same slice."""
        if k < 1:
            raise ValueError(f"speculation depth must be >= 1, got {k}")
        cached = self._spec.get(k)
        if cached is not None:
            return cached
        s, l, w = self.max_slots, self.max_len, k + 1
        d, v = self.cfg.d_model, self.vocab_size
        prog, scratch_startup = fluid.Program(), fluid.Program()
        prog.random_seed = scratch_startup.random_seed = self.seed
        prog._donate_state = True
        with fluid.program_guard(prog, scratch_startup), \
                fluid.unique_name.guard():
            draft = layers.data(self.SP_DRAFT, shape=[s, k],
                                dtype="int64", append_batch_size=False)
            active = layers.data(self.SP_ACTIVE, shape=[s],
                                 dtype="float32", append_batch_size=False)
            if self.paged:
                ptable = layers.data(
                    self.SP_PTABLE, shape=[s, self.pages_per_slot],
                    dtype="int64", append_batch_size=False)
            logit_rows = []
            for j in range(w):
                tokens = layers.data(self.SP_TOK.format(j), shape=[s, 1],
                                     dtype="int64",
                                     append_batch_size=False)
                posenc = layers.data(self.SP_PE.format(j), shape=[s, d],
                                     dtype="float32",
                                     append_batch_size=False)
                bias = layers.data(self.SP_BIAS_J.format(j),
                                   shape=[s, 1, l], dtype="float32",
                                   append_batch_size=False)
                wrow = layers.data(self.SP_WROW.format(j), shape=[s],
                                   dtype="int64", append_batch_size=False)
                woff = layers.data(self.SP_WOFF.format(j), shape=[s],
                                   dtype="int64", append_batch_size=False)

                x = layers.reshape(self._embed(tokens, posenc), [s, 1, d])

                def sub_attn(q, kk, v_, i, bias=bias, wrow=wrow,
                             woff=woff):
                    ck = self._cache_var(f"dlm{i}_cache_k")
                    cv = self._cache_var(f"dlm{i}_cache_v")
                    ck = layers.kv_cache_scatter(
                        ck, layers.reshape(kk, [s, d]), wrow, woff)
                    cv = layers.kv_cache_scatter(
                        cv, layers.reshape(v_, [s, d]), wrow, woff)
                    if self.paged:
                        return layers.paged_attention(
                            layers.scale(q, scale=d ** -0.5), ck, cv,
                            ptable, bias, scale=1.0)         # [S, 1, D]
                    scores = layers.matmul(
                        layers.scale(q, scale=d ** -0.5), ck,
                        transpose_y=True)                    # [S, 1, L]
                    probs = layers.softmax(
                        layers.elementwise_add(scores, bias))
                    return layers.matmul(probs, cv)          # [S, 1, D]

                for i in range(self.cfg.n_layer):
                    x = self._layer(
                        x, i,
                        lambda q, kk, v_, i=i: sub_attn(q, kk, v_, i))
                logit_rows.append(layers.fc(
                    layers.reshape(x, [s, d]), v, bias_attr=False,
                    param_attr=ParamAttr(name="dlm_out_w")))
            logits = layers.concat(
                [layers.reshape(r, [s, 1, v]) for r in logit_rows],
                axis=1)                                      # [S, w, V]
            toks, nacc = layers.spec_accept(logits, draft, mask=active,
                                            end_id=self.end_id)
        out = (prog, toks.name, nacc.name, logit_rows[0].name)
        self._spec[k] = out
        return out

    def weight_names(self):
        """The hot-swap rebind set: every learned weight shared by name
        across the startup/prefill/step family.  Excludes the
        ``dlm{i}_cache_k/v`` slot caches — those are engine-lifetime
        activations of whichever weights wrote them, never checkpoint
        state (a swap that rebound them would tear every in-flight
        stream's K/V prefix)."""
        return sorted(v.name for v in self.startup.list_vars()
                      if v.persistable and "_cache_" not in v.name)

    # -- host-side helpers the engine uses to build tick feeds --

    def posenc_rows(self, positions):
        """pos_table rows for an int position vector (clipped in-range)."""
        idx = np.clip(np.asarray(positions, np.int64), 0, self.max_len - 1)
        return self.pos_table[idx]

    def validity_bias(self, positions):
        """[S, 1, L] additive bias: 0 where cache index <= pos, -inf
        elsewhere.  EXACT -inf on purpose — stale cache rows beyond a
        stream's frontier must contribute exactly zero attention weight
        (IEEE exp(-inf)=0), which is what makes slot reuse invisible to
        the generated bits."""
        pos = np.asarray(positions, np.int64).reshape(-1, 1)
        idx = np.arange(self.max_len, dtype=np.int64)[None, :]
        bias = np.where(idx <= pos, 0.0, -np.inf).astype(np.float32)
        return bias.reshape(len(positions), 1, self.max_len)


def build(cfg=None, src_len=64, tgt_len=64, lr=1e-3, warmup_steps=None):
    """Full training graph with Adam (+ optional noam decay).  Returns
    (src_word, tgt_word, lbl_word, avg_cost)."""
    cfg = cfg or tiny_config()
    src_word, tgt_word, lbl_word, avg_cost, _ = forward(cfg, src_len, tgt_len)
    if warmup_steps:
        with fluid.name_scope("optimizer"):
            lr_sched = layers.learning_rate_scheduler.noam_decay(
                cfg.d_model, warmup_steps)
        opt = fluid.optimizer.Adam(learning_rate=lr_sched,
                                   beta1=0.9, beta2=0.98, epsilon=1e-9)
    else:
        opt = fluid.optimizer.Adam(learning_rate=lr, beta1=0.9, beta2=0.98,
                                   epsilon=1e-9)
    opt.minimize(avg_cost)
    return src_word, tgt_word, lbl_word, avg_cost
