"""BERT-style masked-LM pretraining model (BASELINE config #5 "ERNIE /
BERT-base pretraining (DistributeTranspiler SPMD on pod)").

The reference era predates an in-tree BERT; the config names the
*capability*: a deep bidirectional transformer encoder pretrained with
masked-LM + next-sentence-prediction, trained data/model-parallel on the
pod.  Architecture follows Devlin et al.: learned position + token-type
embeddings, post-LN encoder blocks (reused from models/transformer.py),
an MLM head that gathers the masked positions (so the [B*T, V] logits
matrix never materializes — only [n_mask, V]) and an NSP head on the [CLS]
vector.  All parameters are plain fluid layers, so ParallelExecutor /
ShardedTrainStep shard it like any other program (dp / mp / ZeRO-1).
"""

from __future__ import annotations

import numpy as np

from .. import fluid
from ..fluid import layers
from ..fluid.param_attr import ParamAttr
from .transformer import (Config, _ffn, _multi_head_attention, _padding_bias,
                          _postprocess)


class BertConfig:
    def __init__(self, name, vocab_size=30522, d_model=768, d_inner=3072,
                 n_head=12, n_layer=12, type_vocab_size=2, max_len=512,
                 dropout=0.1, ring_attention=False, stacked=False,
                 n_microbatches=4, recompute=False):
        self.name = name
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.d_inner = d_inner
        self.n_head = n_head
        self.n_layer = n_layer
        self.type_vocab_size = type_vocab_size
        self.max_len = max_len
        self.dropout = dropout
        # ring_attention=True routes every encoder attention through
        # layers.ring_attention: long sequences shard over an "sp" mesh
        # axis (models/transformer.Config.ring_attention semantics)
        self.ring_attention = ring_attention
        # stacked=True builds the encoder as ONE mesh-aware layer-stack op
        # (layers.transformer_encoder_stack): pipeline over "pp", Megatron
        # TP over "mp", ring attention over "sp" — same semantics as
        # models/transformer.Config.stacked; recompute adds per-layer
        # jax.checkpoint for long-sequence memory
        self.stacked = stacked
        self.n_microbatches = n_microbatches
        self.recompute = recompute


def base_config():
    return BertConfig("base")


def tiny_config():
    return BertConfig("tiny", vocab_size=500, d_model=64, d_inner=128,
                      n_head=4, n_layer=2, max_len=64, dropout=0.0)


def _bert_embed(ids, type_ids, cfg, seq_len):
    word = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.d_model],
        param_attr=ParamAttr(name="bert_word_emb"))
    pos_ids = layers.assign(np.arange(seq_len, dtype=np.int64))
    pos = layers.embedding(
        pos_ids, size=[cfg.max_len, cfg.d_model],
        param_attr=ParamAttr(name="bert_pos_emb"))
    typ = layers.embedding(
        type_ids, size=[cfg.type_vocab_size, cfg.d_model],
        param_attr=ParamAttr(name="bert_type_emb"))
    out = layers.elementwise_add(layers.elementwise_add(word, typ), pos)
    out = layers.layer_norm(out, begin_norm_axis=2)
    if cfg.dropout:
        out = layers.dropout(out, dropout_prob=cfg.dropout)
    return out


def encoder_stack(emb, pad_bias, cfg):
    if getattr(cfg, "stacked", False):
        return layers.transformer_encoder_stack(
            emb, bias=pad_bias, n_layer=cfg.n_layer, n_head=cfg.n_head,
            d_inner=cfg.d_inner, dropout=cfg.dropout,
            n_microbatches=getattr(cfg, "n_microbatches", 4),
            recompute=getattr(cfg, "recompute", False))
    enc = emb
    for i in range(cfg.n_layer):
        attn = _multi_head_attention(
            enc, enc, enc, pad_bias, cfg.d_model, cfg.n_head, cfg.dropout,
            prefix=f"bert{i}_self",
            use_ring=getattr(cfg, "ring_attention", False))
        enc = _postprocess(enc, attn, cfg.dropout)
        ff = _ffn(enc, cfg.d_inner, cfg.d_model, prefix=f"bert{i}")
        enc = _postprocess(enc, ff, cfg.dropout)
    return enc


def forward(cfg, seq_len, n_mask):
    """Build the pretraining graph; returns (inputs..., losses, logits).

    Feeds:
      src_ids    int64 [B, seq_len]      token ids (0 = pad)
      type_ids   int64 [B, seq_len]      segment A/B ids
      mask_pos   int64 [B*n_mask]        FLAT positions into [B*T] rows
      mask_label int64 [B*n_mask, 1]     original token at each masked slot
      nsp_label  int64 [B, 1]            is-next-sentence
    """
    src_ids = layers.data(name="src_ids", shape=[seq_len], dtype="int64")
    type_ids = layers.data(name="type_ids", shape=[seq_len], dtype="int64")
    mask_pos = layers.data(name="mask_pos", shape=[1], dtype="int64")
    mask_label = layers.data(name="mask_label", shape=[1], dtype="int64")
    nsp_label = layers.data(name="nsp_label", shape=[1], dtype="int64")

    emb = _bert_embed(src_ids, type_ids, cfg, seq_len)
    pad_bias = _padding_bias(src_ids, seq_len)
    enc = encoder_stack(emb, pad_bias, cfg)   # [B, T, D]

    # MLM head: gather ONLY the masked rows before projecting to the vocab
    # (ref-era models project all B*T rows; gathering first keeps the big
    # [*, V] matmul at n_mask rows — the standard BERT trick, MXU-friendly)
    flat = layers.reshape(enc, shape=[-1, cfg.d_model])     # [B*T, D]
    masked = layers.gather(flat, mask_pos)                  # [B*n_mask, D]
    masked = layers.fc(masked, cfg.d_model, act="relu",
                       param_attr=ParamAttr(name="mlm_transform_w"))
    masked = layers.layer_norm(masked, begin_norm_axis=1)
    mlm_logits = layers.fc(masked, cfg.vocab_size,
                           param_attr=ParamAttr(name="mlm_out_w"))
    mlm_prob = layers.softmax(mlm_logits)
    mlm_loss = layers.mean(layers.cross_entropy(mlm_prob, mask_label))

    # NSP head on the [CLS] (position 0) vector
    cls = layers.slice(enc, axes=[1], starts=[0], ends=[1])
    cls = layers.reshape(cls, shape=[-1, cfg.d_model])
    pooled = layers.fc(cls, cfg.d_model, act="tanh",
                       param_attr=ParamAttr(name="bert_pooler_w"))
    nsp_prob = layers.fc(pooled, 2, act="softmax",
                         param_attr=ParamAttr(name="nsp_out_w"))
    nsp_loss = layers.mean(layers.cross_entropy(nsp_prob, nsp_label))

    total = layers.elementwise_add(mlm_loss, nsp_loss)
    return (src_ids, type_ids, mask_pos, mask_label, nsp_label,
            total, mlm_loss, nsp_loss, mlm_prob)


def build(cfg=None, seq_len=128, n_mask=20, lr=1e-4):
    cfg = cfg or base_config()
    outs = forward(cfg, seq_len, n_mask)
    total = outs[5]
    fluid.optimizer.Adam(learning_rate=lr).minimize(total)
    return outs


def synthetic_batch(cfg, batch, seq_len, n_mask, rng):
    """Deterministic learnable pretraining batch: each sequence is a Markov
    chain (token i -> perm[i] w.p. 0.9), so MLM is genuinely predictable
    from context; NSP label = whether segment B continues the chain."""
    perm = np.random.RandomState(1234).permutation(cfg.vocab_size - 10) + 10
    ids = np.zeros((batch, seq_len), np.int64)
    typ = np.zeros((batch, seq_len), np.int64)
    nsp = np.zeros((batch, 1), np.int64)
    half = seq_len // 2
    for b in range(batch):
        w = int(rng.randint(10, cfg.vocab_size))
        for t in range(seq_len):
            ids[b, t] = w
            nxt = perm[(w - 10) % len(perm)]
            w = int(nxt) if rng.uniform() < 0.9 \
                else int(rng.randint(10, cfg.vocab_size))
        typ[b, half:] = 1
        if rng.uniform() < 0.5:  # corrupt segment B -> not-next
            ids[b, half:] = rng.randint(10, cfg.vocab_size,
                                        size=seq_len - half)
            nsp[b, 0] = 0
        else:
            nsp[b, 0] = 1
    # mask n_mask positions per sequence (avoid position 0 = CLS slot)
    mask_pos = np.zeros((batch * n_mask,), np.int64)
    mask_label = np.zeros((batch * n_mask, 1), np.int64)
    for b in range(batch):
        pos = rng.choice(np.arange(1, seq_len), size=n_mask, replace=False)
        for j, p in enumerate(pos):
            mask_pos[b * n_mask + j] = b * seq_len + p
            mask_label[b * n_mask + j, 0] = ids[b, p]
            ids[b, p] = 1  # [MASK] id
    return {"src_ids": ids, "type_ids": typ, "mask_pos": mask_pos,
            "mask_label": mask_label, "nsp_label": nsp}
