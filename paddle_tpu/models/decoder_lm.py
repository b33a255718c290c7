"""A decoder-only language model from its sizes: pre-norm residual blocks
of grouped-query attention (optionally over a learned per-query selection
of keys) and a routed expert layer of which this program holds a stated
share, RMS norms, rotary positions, an untied head, next-token loss.

Everything is configuration (``Config``); nothing here is specific to one
model or to the benchmark.  The layer, for x = one sequence [T, hidden]::

    h1 = h + Attn(RMSNorm(h));  h2 = h1 + MoE(RMSNorm(h1))
    q = RoPE(RMSNorm_head(x Wq)), k = RoPE(RMSNorm_head(x Wk)), v = x Wv
    S = sparse_indexer(x)            (index_topk set; else every s <= t)
    Attn = concat_h softmax_{s in S_t}(q_h k_{h // group} / sqrt(d)) v  Wo
    MoE = sum over the top_k experts a token chose AND this program holds
          of weight_e W2_e(silu(W1_e x) * W3_e x)

Parameters are created in a fixed order and named ``tok_emb``,
``l<i>_{attn_norm,q_w,k_w,v_w,q_norm,k_norm,idx_q_w,idx_k_w,idx_w_w,o_w,
moe_norm,router_w,w1,w3,w2}``, ``final_norm``, ``lm_head_w``.
"""

from __future__ import annotations

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr


class Config:
    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, expert_width, num_routed,
                 experts_held, experts_per_token, expert_offset=0,
                 norm_topk=True, rms_eps=1e-6, rope_theta=10000.0,
                 index_heads=0, index_head_dim=0, index_topk=0):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not group over "
                             f"{num_kv_heads} key-value heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.expert_width = expert_width
        self.num_routed = num_routed
        self.experts_held = experts_held
        self.experts_per_token = experts_per_token
        self.expert_offset = expert_offset
        self.norm_topk = norm_topk
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        # index_topk 0: plain causal attention, no indexer
        self.index_heads = index_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk


def tiny_config():
    return Config(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, head_dim=16, expert_width=32, num_routed=8,
                  experts_held=4, experts_per_token=2, index_heads=4,
                  index_head_dim=16, index_topk=16)


INIT_STD = 0.02     # every matrix: normal(0, INIT_STD); norm scales: 1


def _attr(name):
    return ParamAttr(name=name, initializer=fluid.initializer.
                     NormalInitializer(0.0, INIT_STD))


def _proj(x, width, name):
    return layers.fc(x, width, num_flatten_dims=2, bias_attr=False,
                     param_attr=_attr(name))


def _heads(x, seq_len, n, cfg, norm_name=None):
    """[B, T, n*Dh] -> [B, n, T, Dh]; normed per head and rotated where
    ``norm_name`` names the norm's scale (q and k; v is neither)."""
    x = layers.reshape(x, [-1, seq_len, n, cfg.head_dim])
    if norm_name is not None:
        x = layers.rms_norm(x, epsilon=cfg.rms_eps,
                            param_attr=ParamAttr(name=norm_name))
        x = layers.rotary_embedding(x, theta=cfg.rope_theta)
    return layers.transpose(x, perm=[0, 2, 1, 3])


def _attention(x, cfg, seq_len, p):
    q = _heads(_proj(x, cfg.num_heads * cfg.head_dim, f"{p}_q_w"),
               seq_len, cfg.num_heads, cfg, f"{p}_q_norm")
    k = _heads(_proj(x, cfg.num_kv_heads * cfg.head_dim, f"{p}_k_w"),
               seq_len, cfg.num_kv_heads, cfg, f"{p}_k_norm")
    v = _heads(_proj(x, cfg.num_kv_heads * cfg.head_dim, f"{p}_v_w"),
               seq_len, cfg.num_kv_heads, cfg)
    sel = None
    if cfg.index_topk:
        sel = layers.sparse_indexer(
            x, cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            theta=cfg.rope_theta, name=f"{p}_idx",
            param_attr=_attr(None))
    ctx = layers.sparse_attention(q, k, v, selection=sel,
                                  scale=cfg.head_dim ** -0.5)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         [-1, seq_len, cfg.num_heads * cfg.head_dim])
    return _proj(ctx, cfg.hidden_size, f"{p}_o_w")


def forward(cfg, seq_len):
    """Data layers, logits and the mean next-token cross-entropy.  Returns
    (tokens, labels, loss, logits); ``labels[b, t]`` is the token that
    follows ``tokens[b, t]``."""
    tokens = layers.data(name="tokens", shape=[seq_len], dtype="int64")
    labels = layers.data(name="labels", shape=[seq_len, 1], dtype="int64")
    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=_attr("tok_emb"))
    for i in range(cfg.num_layers):
        p = f"l{i}"
        x = layers.rms_norm(h, epsilon=cfg.rms_eps,
                            param_attr=ParamAttr(name=f"{p}_attn_norm"))
        h = layers.elementwise_add(h, _attention(x, cfg, seq_len, p))
        x = layers.rms_norm(h, epsilon=cfg.rms_eps,
                            param_attr=ParamAttr(name=f"{p}_moe_norm"))
        h = layers.elementwise_add(h, layers.moe_experts(
            x, cfg.num_routed, cfg.experts_held, cfg.expert_width,
            cfg.experts_per_token, expert_offset=cfg.expert_offset,
            norm_topk=cfg.norm_topk, name=p, param_attr=_attr(None)))
    h = layers.rms_norm(h, epsilon=cfg.rms_eps,
                        param_attr=ParamAttr(name="final_norm"))
    logits = _proj(h, cfg.vocab_size, "lm_head_w")
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
    return tokens, labels, loss, logits


def build(cfg=None, seq_len=64, lr=1e-4, beta1=0.9, beta2=0.95,
          epsilon=1e-8):
    """The training graph with Adam.  Returns (tokens, labels, loss)."""
    cfg = cfg or tiny_config()
    tokens, labels, loss, _ = forward(cfg, seq_len)
    fluid.optimizer.Adam(learning_rate=lr, beta1=beta1, beta2=beta2,
                         epsilon=epsilon).minimize(loss)
    return tokens, labels, loss
