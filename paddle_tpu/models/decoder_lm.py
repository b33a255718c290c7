"""A decoder-only language model from its sizes: residual blocks whose
token mixer is grouped-query attention (over a learned per-query selection
of keys, under a causal window, or plain causal; window layers beside
global ones) or, layer by layer, a gated short convolution, attention
whose keys and values come from a low-rank latent, a gated delta rule
(linear attention with a matrix state a head) or a selective state-space
scan (a Mamba-2 mixer), and a feed-forward that is dense in the leading
layers and elsewhere a routed expert layer of which this program holds a
stated share (SiLU-gated experts of three matrices, or squared-ReLU ones of
two), RMS norms, rotary positions, a head of its own or the embedding's
transpose, next-token loss and, where the model has one, a multi-token
module's loss beside it; or, where ``block_diffusion`` says so, a step of
diffusion over blocks: a clean and a noised copy of every sequence, an
attention that is causal over blocks and bidirectional inside one, and a
loss on the masked tokens weighted by their block's noise level.  A
published layer is a mixer and then a feed-forward, or, where
``sub_blocks`` says so, one of the two alone.

Everything is configuration (``Config``); nothing here is specific to one
model or to the benchmark.  The layer, for x = one sequence [T, hidden] and
layer i (PUBLISHED index ``layer_offset + i``; [..] marks what a ``Config``
field turns on)::

    h0 = Emb[tokens] [* embed_scale]
    a  = RMSNorm(x)
    conv layer (mixers[published index] == "conv"):
        [B | C | u] = a Win                          three chunks of hidden
        c[t] = sum_j w[:, j] (B * u)[t - (conv_taps - 1) + j], zero before 0
        y  = (C * c) Wout
    attention layer (every layer where mixers is None), down to ``y``:
    q  = a Wq, k = a Wk, v = a Wv  [g = a Wg]
    q  = RMSNorm_head(q), k = RMSNorm_head(k)        per head [not where
                                                     qk_norm is off]
    window layer: q, k = RoPE(q, k) [on the head's first rotary_dims
                  columns only]; key s counts for query t iff
                  0 <= t - s < window
    global layer ((published index + 1) % global_every == 0, or every layer
                  where there is no window): q, k = RoPE(q, k) [not where
                  rope_global is off; global_rotary: by the frequencies
                  G.inv_freq in rope_theta's place, G = cfg.global_rotary];
                  key s counts iff s <= t [and s in S_t =
                  sparse_indexer(a), where index_topk is set]
    o  = concat_h softmax_{keys that count}(q_h k_{h // group} / sqrt(d)) v
         [global_rotary, in a global layer: * G.scale in 1 / sqrt(d)'s
         place; a window layer keeps rope_theta's table and 1 / sqrt(d)]
    y  = (o [* sigmoid(g)]) Wo
    latent layer (mixers[published index] == "latent"; L = cfg.latent):
        q = a Wq                      [T, H, L.nope + L.rope]
        [c | kr] = a Wkva             L.rank + L.rope
        [k_nope | v] = RMSNorm(c) Wkvb      [T, H, L.nope + L.value]
        k = [k_nope | kr, the same for every head]
        q = RMSNorm_head(q), k = RMSNorm_head(k)      [not where L.head_norm
                                                       is off]
        q, k = RoPE on their last L.rope columns only, pairs (2i, 2i + 1)
               where L.interleaved, by the frequencies L.inv_freq [not
               where L.rotary is off: a layer without positions]
        o = concat_h softmax_{s <= t}(q_h k_h * L.scale) v_h    v_h L.value
                                      wide, which need not be head_dim
        y = (o [* sigmoid(g)]) Wo
    delta layer (mixers[published index] == "delta"; R = cfg.delta, Hk key
                 heads of dk, Hv value heads of dv):
        [q | k | v | z] = a Wqkvz         Hk dk, Hk dk, Hv dv, Hv dv wide
        [b | al] = a Wba                  Hv each, float32 from here on
        [q | k | v] = SiLU(filter([q | k | v]))   one causal R.taps-tap
                                          filter a channel, zero before 0
        beta = sigmoid(b); g = -exp(A_log) * softplus(al + dt_bias)
        q = l2norm(q) * dk ** -0.5, k = l2norm(k)      per head, eps 1e-6
        per value head h (key head h // (Hv / Hk)), S_0 = 0 [dk, dv]:
            S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
            (computed R.chunk tokens at a time: ops/delta_rule.py)
        y = (RMSNorm_head(o) * SiLU(z)) Wout      one scale of dv
        [W = cfg.delta_gates; W.decay_rank r: the decay is a VECTOR along
            the key, [T, Hv, dk], g = -exp(A_log_h) * softplus((a Wf1) Wf2
            + dt_bias), Wf1 [hidden, r], Wf2 [r, Hv dk], dt_bias a channel
            and A_log a head; S' = Diag(exp(g_t)) S_{t-1}; beta = sigmoid(a Wb)]
        [W.gate_rank r: z = (a Wg1) Wg2, Wg1 [hidden, r], and no z in the
            projection in; W.gate "sigmoid": sigmoid(z) in SiLU(z)'s place]
    ssm layer (mixers[published index] == "ssm"; M = cfg.ssm, H heads of P
               columns, G groups of N state columns):
        [z | xBC | dt] = a Win            H P | H P + 2 G N | H wide
        xBC = SiLU(filter(xBC) [+ b_conv])    one causal M.taps-tap filter a
                                          channel, zero before 0 [M.conv_bias]
        [u | B | C] = xBC                 H P | G N | G N; head h reads group
                                          h // (H / G)
        delta = softplus(dt + dt_bias);  A = -exp(A_log)   [H], float32
        per head h, S_0 = 0 [P, N]:
            S_t = exp(delta_t A) S_{t-1} + delta_t u_t B_t^T
            o_t = S_t C_t + D u_t
            (computed M.chunk tokens at a time: ops/ssd.py)
        y = RMSNorm_groups(o * SiLU(z)) Wout    the gate FIRST, the mean over
                                          each of G groups of H P / G columns,
                                          one scale of H P
    x1 = x + y                      [post_norms: x + RMSNorm(y)]
    m  = RMSNorm(x1)
    dense layer (published index < dense_layers):
        f = W2(silu(W1 m) * W3 m)                     width dense_width
        [expert_gate off, here, in Shared and in every routed expert: two
            matrices about a squared ReLU, W2 relu(W1 m)^2, and no W3]
    routed layer:
        s = softmax(m Wr) [or sigmoid(m Wr)] over all num_routed experts
        E = top experts_per_token of s [+ b: a bias that chooses only]
        w_e = s_e [/ (sum_E s + route_norm_eps)] [* route_scale]
        f = [Shared(m) +] sum over e in E AND held here of
            w_e W2_e(silu(W1_e m) * W3_e m)
        Shared: the dense feed-forward at width shared_width [shared_gate:
            times sigmoid(m w_sg), one number a token]
    x2 = x1 + f                     [post_norms: x1 + RMSNorm(f)]
    [sub_blocks[published index] "mixer": the layer ends at x1, it has no
        m and no f; "ffn": it has no a and no y, m = RMSNorm(x) and
        x2 = x + f: one norm and one residual add a published layer]
    [residual "farskip": a sub-block reads the stream WITHOUT the sub-block
        just before it.  With s_0 = h0 = s_{-1} and the sub-blocks F_j
        numbered mixer, feed-forward, mixer, ...:
        s_j = s_{j-1} + F_j(RMSNorm_j(s_{j-2})); "sequential", the default
        and the lines above: s_j = s_{j-1} + F_j(RMSNorm_j(s_{j-1}))]
    logits = RMSNorm(x_last) Whead [tie_head: Emb^T, one parameter whose
        gradient is the lookup's rows plus the head product's]
    loss = mean next-token cross-entropy
    [block_diffusion D: the program reads ``tokens`` x [B, L], ``noised``
        x~ [B, L] (x with some tokens replaced by D.mask_id, block by block
        of D.block, each block at a level t_b of its own: ``noise``) and
        ``weights`` [B, L] (m_i / t_B(i), m_i 1 where x~_i is the mask;
        B(i) = i // D.block).  The trunk runs on 2L rows, [x | x~]; index i
        and index L + i both have rotary position i; every op but attention
        acts on each row alone.  Attention, in one softmax a query:
            a clean query c_i counts the clean keys c_j with B(j) <= B(i)
                (its own block whole, later tokens of it too);
            a noised query n_i the clean keys c_j with B(j) < B(i) and the
                noised keys n_j with B(j) = B(i);
            no clean query counts a noised key.
        The head reads the L noised rows alone and position i's logits
        predict x_i itself (no shift):
        loss = sum_i weights_i * CE(logits_i, x_i) / (B L)]
    [mtp_depth 1, the multi-token module after the trunk:
        h' = [RMSNorm(x_last) | RMSNorm(Emb[labels])] Wmerge   the SAME Emb
        u  = one more routed block on h' (the mixer of the last published
             layer, a global one; the residual rule, from s_0 = h')
        logits' = RMSNorm(u) Whead                             the SAME head
        loss += mtp_weight * mean cross-entropy(logits', labels2)]
    after each step [route_bias_coeff], per routed layer:
        b_e += route_bias_coeff * sign(mean_e'(n_e') - n_e)
        n_e = the step's assignments to expert e, over ALL num_routed;
        b starts at 0, is persistable and gets no gradient

Parameters are created in a fixed order and named ``tok_emb``,
``l<i>_{attn_norm,q_w,q_norm,k_w,k_norm,v_w,idx_q_w,idx_k_w,idx_w_w,gate_w,
o_w,post_attn_norm}`` (the head norms only where ``qk_norm``; a conv layer: ``l<i>_{conv_norm,conv_in_w,conv_w,
conv_out_w,post_attn_norm}`` and none of the others; a latent layer:
``l<i>_{attn_norm,q_w,q_norm,kva_w,kv_norm,kvb_w,k_norm,gate_w,o_w,
post_attn_norm}``, the head norms only where it has them; a delta layer:
``l<i>_{attn_norm,qkvz_w,ba_w,conv_w,dt_bias,a_log,delta_norm,o_w,
post_attn_norm}``, with ``qkv_w,g1_w,g2_w`` in ``qkvz_w``'s place under a
gate rank and ``b_w`` in ``ba_w``'s, ``f1_w,f2_w`` after ``conv_w`` under a
decay rank; an ssm layer: ``l<i>_{ssm_norm,ssm_in_w,conv_w,conv_b,dt_bias,
a_log,ssm_d,gate_norm,o_w,post_attn_norm}``), then
``l<i>_{mlp_norm,mlp_w1,mlp_w3,mlp_w2}`` (dense)
or ``l<i>_{moe_norm,shared_w1,shared_w3,shared_w2,shared_gate_w,router_w,w1,
w3,w2}`` (routed; ``l<i>_route_bias`` is no parameter; no ``w3`` of any kind
where ``expert_gate`` is off), ``l<i>_post_mlp_norm`` (a layer of one
sub-block has that sub-block's names and none of the other's),
``final_norm``, ``lm_head_w`` (not with ``tie_head``), then the module's
``mtp_{h_norm,e_norm,merge_w}``, its block's as a routed layer's under
``mtp_`` for ``l<i>_``, and ``mtp_norm``; ``i`` counts the layers held,
from 0.
"""

from __future__ import annotations

import contextlib
import math
from typing import NamedTuple, Optional, Tuple

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr


MIXERS = ("attention", "conv", "latent", "delta", "ssm")
SUB_BLOCKS = ("both", "mixer", "ffn")   # what a published layer is made of
GATES = ("silu", "sigmoid")         # of a delta mixer's output
RESIDUALS = ("sequential", "farskip")


class Latent(NamedTuple):
    """What a ``latent`` mixer needs beyond the model's heads: the rank of
    the latent that keys and values are made from, the unrotated and the
    rotated width of a query's and a key's head (``Config.head_dim`` is
    their sum), the value's width, and the rotary of the rotated part: its
    ``rope // 2`` frequencies (None: ``rope_theta``'s own), its pairing,
    and the softmax scale where it is not ``head_dim ** -0.5``.  ``rotary``
    off: a layer without positions, both parts as they come; ``head_norm``
    off: no per-head norm on queries and keys."""
    rank: int
    nope: int
    rope: int
    value: int
    inv_freq: Optional[Tuple[float, ...]] = None
    interleaved: bool = False
    scale: float = 0.0
    rotary: bool = True
    head_norm: bool = True


class Delta(NamedTuple):
    """What a ``delta`` mixer needs, none of it the model's attention
    heads: its key heads (queries have as many) and value heads (a multiple
    of them), the width of each, the taps of the causal filter in front of
    the rule, and the tokens the rule works at once."""
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int = 4
    chunk: int = 64


class Ssm(NamedTuple):
    """What an ``ssm`` mixer needs, none of it the model's attention heads:
    its heads and their width, the groups that share a B and a C and the
    width of those (the state's columns), the taps of the causal filter in
    front of the scan, the tokens the scan works at once, and whether the
    filter has a bias."""
    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int = 4
    chunk: int = 128
    conv_bias: bool = True


class DeltaGates(NamedTuple):
    """Where a ``delta`` mixer's two gates come from, and the second one's
    form; every default is a column of the mixer's projections in.
    ``decay_rank`` r > 0: the decay is a VECTOR along the key, made by a
    pair of products through r columns (0: one number a value head, a
    column of the projection ``ba``); ``gate_rank`` r > 0: the output's gate
    comes through such a pair too (0: a chunk of the projection in);
    ``gate``: its form, one of GATES."""
    decay_rank: int = 0
    gate: str = "silu"
    gate_rank: int = 0


class Rotary(NamedTuple):
    """The rotary of the GLOBAL plain-attention layers where it is not the
    window layers': the frequencies of the rotated columns' pairs
    (``rotary_dims // 2`` of them, ``head_dim // 2`` where the whole head
    turns) in ``rope_theta``'s place, and the softmax scale where it is not
    ``head_dim ** -0.5`` (a table that a scaling rule made brings a
    temperature: ``yarn_inv_freq``, ``yarn_softmax_scale``)."""
    inv_freq: Tuple[float, ...]
    scale: float = 0.0


class BlockDiffusion(NamedTuple):
    """Training by diffusion over blocks in next-token training's place:
    the length of a block (a sequence is a whole number of them) and the id
    that stands for a masked token, which no data token has.  Every layer
    is then a plain attention layer without a window or a selection: what
    a noised token may see is defined for attention alone."""
    block: int
    mask_id: int


DELTA_NORM_EPS = 1e-6   # the l2 norm of a delta mixer's queries and keys
DT_BIAS_INIT = -3.0     # softplus(-3) = 0.049: a token forgets a twentieth


def yarn_inv_freq(dims, base, factor, original_positions, beta_fast=32,
                  beta_slow=1):
    """The ``dims // 2`` rotary frequencies under YaRN: ``base ** (-2i /
    dims)`` for the pairs that turn more than ``beta_fast`` times over the
    ``original_positions``, that over ``factor`` for those that turn fewer
    than ``beta_slow`` times, and a linear blend between."""
    def pair_that_turns(r):
        return dims * math.log(original_positions / (2 * math.pi * r)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dims - 1)
    out = []
    for i in range(dims // 2):
        f = base ** (-2.0 * i / dims)
        ramp = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return tuple(out)


def yarn_softmax_scale(head_dim, factor, mscale_all_dim=1.0):
    """``head_dim ** -0.5 * m ** 2`` with ``m = 0.1 * mscale_all_dim *
    ln(factor) + 1``: the temperature YaRN puts on attention beside its
    blended frequencies."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return head_dim ** -0.5 * m * m


class Config:
    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 num_kv_heads, head_dim, expert_width, num_routed,
                 experts_held, experts_per_token, expert_offset=0,
                 norm_topk=True, rms_eps=1e-6, rope_theta=10000.0,
                 index_heads=0, index_head_dim=0, index_topk=0,
                 window=0, global_every=0, rope_global=True, layer_offset=0,
                 attn_gate=False, post_norms=False, embed_scale=1.0,
                 dense_layers=0, dense_width=0, shared_width=0,
                 router_score="softmax", route_norm_eps=0.0,
                 route_scale=1.0, route_bias_coeff=0.0, mixers=None,
                 conv_taps=0, tie_head=False, latent=None,
                 residual="sequential", mtp_depth=0, mtp_weight=0.0,
                 delta=None, rotary_dims=0, shared_gate=False,
                 global_rotary=None, delta_gates=None, ssm=None,
                 sub_blocks=None, expert_gate=True, qk_norm=True,
                 block_diffusion=None):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not group over "
                             f"{num_kv_heads} key-value heads")
        if window and index_topk:
            raise ValueError("a window and a learned selection in one "
                             "model: no layer kind is defined for both")
        if dense_layers > layer_offset and not dense_width:
            raise ValueError("a leading dense layer needs dense_width")
        parts = (sub_blocks or ())[layer_offset:layer_offset + num_layers]
        if sub_blocks is not None and (
                len(parts) != num_layers or set(parts) - set(SUB_BLOCKS)
                or mixers is None or mtp_depth):
            raise ValueError(
                f"sub_blocks names {list(parts)} for the {num_layers} "
                f"layers from {layer_offset} on: one of {SUB_BLOCKS} each, "
                "beside `mixers`, and with no multi-token module (its block "
                "is the last published layer's, both sub-blocks)")
        held = (mixers or ())[layer_offset:layer_offset + num_layers]
        # a layer that is a feed-forward alone names no mixer
        takes = [(None,) if part == "ffn" else MIXERS
                 for part in parts or ["both"] * len(held)]
        if mixers is not None and (len(held) != num_layers or any(
                m not in ok for m, ok in zip(held, takes))):
            raise ValueError(
                f"mixers names {list(held)} for the {num_layers} layers "
                f"from {layer_offset} on: one of {MIXERS} each (None where "
                "sub_blocks says the layer is a feed-forward alone)")
        if "conv" in held and conv_taps < 1:
            raise ValueError("a conv layer needs conv_taps")
        if mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {mtp_depth}: one module after the "
                             "trunk is what is built, or none")
        if latent is not None:
            latent = Latent(*latent)
            if latent.nope + latent.rope != head_dim or latent.rope % 2 \
                    or latent.value < 1 or num_kv_heads != num_heads:
                raise ValueError(
                    f"latent heads of {latent.nope} + {latent.rope} and "
                    f"values of {latent.value} beside head_dim {head_dim} "
                    f"and {num_heads}/{num_kv_heads} heads: a query's and "
                    "a key's two parts add up to head_dim, the rotated one "
                    "even, and every query head has its own key and value")
        elif "latent" in held or (mtp_depth and mixers
                                  and mixers[-1] == "latent"):
            raise ValueError("a latent layer needs the record `latent`")
        if delta is not None:
            delta = Delta(*delta)
            if delta.value_heads % delta.key_heads or delta.taps < 1 \
                    or delta.chunk < 1:
                raise ValueError(
                    f"{delta}: the value heads are a multiple of the key "
                    "heads, and a filter and a chunk hold a token at least")
        elif "delta" in held or (mtp_depth and mixers
                                 and mixers[-1] == "delta"):
            raise ValueError("a delta layer needs the record `delta`")
        if ssm is not None:
            ssm = Ssm(*ssm)
            if ssm.heads % ssm.groups or min(ssm.head_dim, ssm.state,
                                             ssm.taps, ssm.chunk) < 1:
                raise ValueError(
                    f"{ssm}: the heads are a multiple of the groups, and a "
                    "head, a state, a filter and a chunk hold a column or a "
                    "token at least")
        if "ssm" in held and ssm is None or (
                mtp_depth and mixers and mixers[-1] == "ssm"):
            raise ValueError("an ssm layer needs the record `ssm`, and no "
                             "multi-token module is defined on one")
        delta_gates = DeltaGates(*(delta_gates or ()))
        if delta_gates.gate not in GATES or min(
                delta_gates.decay_rank, delta_gates.gate_rank) < 0:
            raise ValueError(f"{delta_gates}: the gate is one of {GATES} "
                             "and a rank is 0 or more")
        if rotary_dims % 2 or not 0 <= rotary_dims <= head_dim:
            raise ValueError(f"rotary_dims {rotary_dims}: an even part of "
                             f"the head's {head_dim} columns, or 0 for all")
        if global_rotary is not None:
            inv_freq, *rest = global_rotary
            global_rotary = Rotary(tuple(inv_freq), *rest)
            pairs = (rotary_dims or head_dim) // 2
            if not rope_global or len(global_rotary.inv_freq) != pairs:
                raise ValueError(
                    f"global_rotary with {len(global_rotary.inv_freq)} "
                    f"frequencies beside rope_global {rope_global}: the "
                    f"global layers' own table, one frequency for each of "
                    f"the {pairs} pairs they rotate, and no such layer goes "
                    "without positions")
        if shared_gate and not (shared_width and expert_gate):
            raise ValueError("a gate on the shared expert needs "
                             "shared_width, and gated experts")
        if residual not in RESIDUALS:
            raise ValueError(f"residual {residual!r}: one of {RESIDUALS}")
        if block_diffusion is not None:
            block_diffusion = BlockDiffusion(*block_diffusion)
            if block_diffusion.block < 1 \
                    or not 0 <= block_diffusion.mask_id < vocab_size \
                    or mixers is not None or window or index_topk \
                    or mtp_depth:
                raise ValueError(
                    f"{block_diffusion} beside a vocabulary of "
                    f"{vocab_size}: a block holds a token at least, the "
                    "mask is an id of the vocabulary, and every layer is "
                    "plain attention (no `mixers`, window, selection or "
                    "multi-token module: what a noised token sees is "
                    "defined for none of them)")
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
        # window 0: every layer global.  global_every n: of the PUBLISHED
        # layers every n-th is global (0: none is, where there is a window)
        self.window = window
        self.global_every = global_every
        self.rope_global = rope_global
        # the published index of the first layer held: dense_layers and
        # global_every keep their published values under a cut in depth
        self.layer_offset = layer_offset
        self.attn_gate = attn_gate
        self.post_norms = post_norms
        self.embed_scale = embed_scale
        self.dense_layers = dense_layers
        self.dense_width = dense_width
        self.shared_width = shared_width
        self.router_score = router_score
        self.route_norm_eps = route_norm_eps
        self.route_scale = route_scale
        self.route_bias_coeff = route_bias_coeff
        # the token mixer of every PUBLISHED layer, one of MIXERS (None:
        # attention everywhere); read at layer_offset + i
        self.mixers = None if mixers is None else tuple(mixers)
        self.conv_taps = conv_taps
        self.tie_head = tie_head
        # what a "latent" mixer needs: a Latent (or its fields in order)
        self.latent = latent
        # which stream a sub-block reads: "farskip" the one without the
        # sub-block just before it
        self.residual = residual
        # the multi-token module: how many (0 or 1) and its loss's weight
        self.mtp_depth = mtp_depth
        self.mtp_weight = mtp_weight
        # what a "delta" mixer needs: a Delta (or its fields in order)
        self.delta = delta
        # the leading columns of a plain attention head that the rotary
        # turns (0: the whole head)
        self.rotary_dims = rotary_dims
        # the shared expert's output times sigmoid(m w_sg)
        self.shared_gate = shared_gate
        # the global plain-attention layers' own rotary table and softmax
        # scale: a Rotary (or its fields in order); None: the window
        # layers' rope_theta and head_dim ** -0.5
        self.global_rotary = global_rotary
        # where a "delta" mixer's gates come from: a DeltaGates (given as
        # one, as its fields in order, or not at all: columns of the
        # mixer's projections in)
        self.delta_gates = delta_gates
        # what an "ssm" mixer needs: an Ssm (or its fields in order)
        self.ssm = ssm
        # what every PUBLISHED layer is made of, one of SUB_BLOCKS (None:
        # both sub-blocks everywhere); read at layer_offset + i
        self.sub_blocks = None if sub_blocks is None else tuple(sub_blocks)
        # off: every feed-forward (dense, shared, routed) is two matrices
        # about a squared ReLU, not three about a SiLU gate
        self.expert_gate = bool(expert_gate)
        # off: a plain attention layer's queries and keys go unnormed (a
        # latent layer has its own switch, ``Latent.head_norm``)
        self.qk_norm = bool(qk_norm)
        # a BlockDiffusion (or its fields in order): the step is one of
        # diffusion over blocks; None: next-token training
        self.block_diffusion = block_diffusion

    def layer_parts(self, i):
        """What held layer ``i`` is made of, one of SUB_BLOCKS."""
        if self.sub_blocks is None:
            return "both"
        return self.sub_blocks[self.layer_offset + i]

    def layer_mixer(self, i):
        """The kind of held layer ``i``'s token mixer (None: it has none)."""
        if self.mixers is None:
            return "attention"
        return self.mixers[self.layer_offset + i]

    def mtp_mixer(self):
        """The kind of the module's block: the last published layer's."""
        return "attention" if self.mixers is None else self.mixers[-1]

    def layer_window(self, i):
        """The window of held layer ``i``: 0 where it is a global one."""
        published = self.layer_offset + i
        if self.global_every and (published + 1) % self.global_every == 0:
            return 0
        return self.window

    def layer_is_dense(self, i):
        return self.layer_offset + i < self.dense_layers


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


def _norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.rms_eps,
                           param_attr=ParamAttr(name=name))


def _heads(x, seq_len, n, cfg, norm_name=None, rotate=False, inv_freq=None):
    """[B, T, n*Dh] -> [B, n, T, Dh]; normed per head where ``norm_name``
    names the norm's scale, and then rotated where ``rotate``: by
    ``rope_theta``'s table, or by ``inv_freq`` (q and k; v is neither).
    Under ``block_diffusion`` T is two copies of a sequence, whose
    positions are equal."""
    x = layers.reshape(x, [-1, seq_len, n, cfg.head_dim])
    if norm_name is not None:
        x = _norm(x, cfg, norm_name)
    if rotate:
        x = layers.rotary_embedding(
            x, theta=cfg.rope_theta, dims=cfg.rotary_dims, inv_freq=inv_freq,
            period=seq_len // 2 if cfg.block_diffusion else 0)
    return layers.transpose(x, perm=[0, 2, 1, 3])


def _gated_out(ctx, x, cfg, seq_len, p, value_dim=0):
    """[B, H, T, Dh] -> the mixer's output: heads side by side, the gate
    where the model has one, the output projection.  ``value_dim``: the
    heads' width where it is not ``head_dim``."""
    width = cfg.num_heads * (value_dim or cfg.head_dim)
    ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                         [-1, seq_len, width])
    if cfg.attn_gate:
        ctx = layers.elementwise_mul(
            ctx, layers.sigmoid(_proj(x, width, f"{p}_gate_w")))
    return _proj(ctx, cfg.hidden_size, f"{p}_o_w")


def _attention(x, cfg, seq_len, p, window, own=None):
    """``own``: the ``Rotary`` of a global layer that has one; its table
    and scale stand in ``rope_theta``'s and ``head_dim ** -0.5``'s place."""
    rotate = bool(window) or cfg.rope_global
    table = own.inv_freq if own else None
    width = cfg.num_heads * cfg.head_dim
    q_norm, k_norm = (f"{p}_{n}_norm" if cfg.qk_norm else None
                      for n in "qk")
    q = _heads(_proj(x, width, f"{p}_q_w"),
               seq_len, cfg.num_heads, cfg, q_norm, rotate, table)
    k = _heads(_proj(x, cfg.num_kv_heads * cfg.head_dim, f"{p}_k_w"),
               seq_len, cfg.num_kv_heads, cfg, k_norm, rotate, table)
    v = _heads(_proj(x, cfg.num_kv_heads * cfg.head_dim, f"{p}_v_w"),
               seq_len, cfg.num_kv_heads, cfg)
    sel = None
    if cfg.index_topk:
        sel = layers.sparse_indexer(
            x, cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            theta=cfg.rope_theta, name=f"{p}_idx",
            param_attr=_attr(None))
    ctx = layers.sparse_attention(
        q, k, v, selection=sel, window=window,
        scale=own.scale if own and own.scale else cfg.head_dim ** -0.5,
        block_rule=(seq_len // 2, cfg.block_diffusion.block)
        if cfg.block_diffusion else None)
    return _gated_out(ctx, x, cfg, seq_len, p)


def _latent_attention(x, cfg, seq_len, p):
    """Attention whose keys and values come from a normed low-rank latent:
    every head's key is an unrotated part of its own beside ONE rotated
    part that all heads share; the query has the same two parts.  What is
    the latent's own (its two products, its norm, the shared key spread
    over the heads, the key's head norm and both partial rotaries) runs
    under the name scope ``latent``."""
    lat, heads = cfg.latent, cfg.num_heads

    def rotated(t):
        if not lat.rotary:
            return t
        return layers.rotary_embedding(
            t, theta=cfg.rope_theta, start=lat.nope, dims=lat.rope,
            interleaved=lat.interleaved, inv_freq=lat.inv_freq)

    def head_normed(t, name):
        return _norm(t, cfg, name) if lat.head_norm else t

    q = head_normed(
        layers.reshape(_proj(x, heads * cfg.head_dim, f"{p}_q_w"),
                       [-1, seq_len, heads, cfg.head_dim]), f"{p}_q_norm")
    with fluid.name_scope("latent"):
        q = layers.transpose(rotated(q), perm=[0, 2, 1, 3])
        c, kr = layers.split(_proj(x, lat.rank + lat.rope, f"{p}_kva_w"),
                             [lat.rank, lat.rope], dim=-1)
        kv = layers.reshape(
            _proj(_norm(c, cfg, f"{p}_kv_norm"),
                  heads * (lat.nope + lat.value), f"{p}_kvb_w"),
            [-1, seq_len, heads, lat.nope + lat.value])
        k_nope, v = layers.split(kv, [lat.nope, lat.value], dim=-1)
        kr = layers.expand(layers.reshape(kr, [-1, seq_len, 1, lat.rope]),
                           [1, 1, heads, 1])
        k = rotated(head_normed(layers.concat([k_nope, kr], axis=-1),
                                f"{p}_k_norm"))
        k = layers.transpose(k, perm=[0, 2, 1, 3])
        v = layers.transpose(v, perm=[0, 2, 1, 3])
    ctx = layers.sparse_attention(
        q, k, v, scale=lat.scale or cfg.head_dim ** -0.5)
    return _gated_out(ctx, x, cfg, seq_len, p, lat.value)


def _short_conv(x, cfg, p):
    """The mixer of a conv layer: one projection to both gates and the
    filter's input, the gated causal filter, one projection back."""
    y = layers.short_conv(_proj(x, 3 * cfg.hidden_size, f"{p}_conv_in_w"),
                          cfg.conv_taps, param_attr=_attr(f"{p}_conv_w"))
    return _proj(y, cfg.hidden_size, f"{p}_conv_out_w")


def _head_param(p, name, value, width):
    """A float32 parameter a head (or channel) of a recurrent mixer, one
    constant at first."""
    return layers.create_parameter(
        [width], "float32", attr=ParamAttr(name=f"{p}_{name}"),
        default_initializer=fluid.initializer.ConstantInitializer(value))


def _delta_mixer(x, cfg, seq_len, p):
    """The gated delta rule between one projection in (queries, keys,
    values and the output's gate side by side; the rule's two gates beside
    them) and one out.  What is not one of those plain products (the
    filter, the gates, the rule, the gated head norm) runs under the name
    scope ``delta``; under a decay or a gate rank the pairs of products
    through the rank, with the decay's softplus and the gate's form, run
    beneath it under ``gates``, and the projections in carry no column of
    theirs."""
    dl, dg = cfg.delta, cfg.delta_gates
    heads = dl.value_heads
    keys, values = dl.key_heads * dl.key_dim, heads * dl.value_dim
    form = layers.swish if dg.gate == "silu" else layers.sigmoid
    qkvz = _proj(x, 2 * keys + values, f"{p}_qkv_w") if dg.gate_rank \
        else _proj(x, 2 * keys + 2 * values, f"{p}_qkvz_w")
    ba = _proj(x, heads, f"{p}_b_w") if dg.decay_rank \
        else _proj(x, 2 * heads, f"{p}_ba_w")

    def pair(rank, width, name):
        return _proj(_proj(x, rank, f"{p}_{name}1_w"), width,
                     f"{p}_{name}2_w")

    def gate_param(name, value, width=heads):
        return _head_param(p, name, value, width)

    with fluid.name_scope("delta"):
        if dg.gate_rank:
            qkv = qkvz
            with fluid.name_scope("gates"):
                gate = form(pair(dg.gate_rank, values, "g"))
        else:
            qkv, z = layers.split(qkvz, [2 * keys + values, values], dim=-1)
        qkv = layers.short_conv(qkv, dl.taps, gated=False,
                                param_attr=_attr(f"{p}_conv_w"))
        q, k, v = layers.split(qkv, [keys, keys, values], dim=-1)
        # the gates in float32: their sums along a chunk are exponents
        if dg.decay_rank:
            b = layers.cast(ba, "float32")
            with fluid.name_scope("gates"):
                al = layers.cast(pair(dg.decay_rank, heads * dl.key_dim,
                                      "f"), "float32")
                decay = layers.elementwise_mul(
                    layers.reshape(layers.softplus(layers.elementwise_add(
                        al, gate_param("dt_bias", DT_BIAS_INIT,
                                       heads * dl.key_dim))),
                        [-1, seq_len, heads, dl.key_dim]),
                    layers.exp(gate_param("a_log", 0.0)), axis=2)
        else:
            b, al = layers.split(layers.cast(ba, "float32"),
                                 [heads, heads], dim=-1)
            decay = layers.elementwise_mul(
                layers.softplus(layers.elementwise_add(
                    al, gate_param("dt_bias", DT_BIAS_INIT))),
                layers.exp(gate_param("a_log", 0.0)))
        o = layers.gated_delta_rule(
            layers.reshape(q, [-1, seq_len, dl.key_heads, dl.key_dim]),
            layers.reshape(k, [-1, seq_len, dl.key_heads, dl.key_dim]),
            layers.reshape(v, [-1, seq_len, heads, dl.value_dim]),
            layers.scale(decay, scale=-1.0), layers.sigmoid(b),
            chunk=dl.chunk, scale=dl.key_dim ** -0.5,
            norm_eps=DELTA_NORM_EPS)
        o = layers.elementwise_mul(
            layers.reshape(_norm(o, cfg, f"{p}_delta_norm"),
                           [-1, seq_len, values]),
            gate if dg.gate_rank else form(z))
    return _proj(o, cfg.hidden_size, f"{p}_o_w")


def _ssm_mixer(x, cfg, seq_len, p):
    """The selective state-space scan between one projection in (the
    output's gate, the filter's input and the step side by side) and one
    out.  What is not one of those plain products (the filter, the step and
    the decay, the scan, the gated group norm) runs under the name scope
    ``ssm``."""
    sm = cfg.ssm
    inner, bc = sm.heads * sm.head_dim, sm.groups * sm.state
    proj = _proj(x, 2 * inner + 2 * bc + sm.heads, f"{p}_ssm_in_w")
    observe.registry().inc("models.decoder.ssm", labels={
        "heads": str(sm.heads), "groups": str(sm.groups),
        "state": str(sm.state), "conv_bias": str(int(sm.conv_bias))})
    with fluid.name_scope("ssm"):
        z, xbc, dt = layers.split(proj, [inner, inner + 2 * bc, sm.heads],
                                  dim=-1)
        xbc = layers.short_conv(
            xbc, sm.taps, gated=False, param_attr=_attr(f"{p}_conv_w"),
            bias_attr=ParamAttr(name=f"{p}_conv_b") if sm.conv_bias
            else None)
        u, b, c = layers.split(xbc, [inner, bc, bc], dim=-1)
        # the step and the decay in float32: their sums along a chunk are
        # exponents
        delta = layers.softplus(layers.elementwise_add(
            layers.cast(dt, "float32"),
            _head_param(p, "dt_bias", DT_BIAS_INIT, sm.heads)))
        a = layers.scale(layers.exp(_head_param(p, "a_log", 0.0, sm.heads)),
                         scale=-1.0)
        o = layers.ssd_scan(
            layers.reshape(u, [-1, seq_len, sm.heads, sm.head_dim]), delta,
            a, b, c, _head_param(p, "ssm_d", 1.0, sm.heads), chunk=sm.chunk,
            groups=sm.groups)
        o = layers.rms_norm(
            layers.elementwise_mul(layers.reshape(o, [-1, seq_len, inner]),
                                   layers.swish(z)),
            epsilon=cfg.rms_eps, param_attr=ParamAttr(name=f"{p}_gate_norm"),
            groups=sm.groups)
    return _proj(o, cfg.hidden_size, f"{p}_o_w")


def _feed_forward(x, cfg, width, p):
    """W2(silu(W1 x) * W3 x), no bias: ``<p>_w1`` gate, ``_w3`` up, ``_w2``
    down; ``expert_gate`` off: W2 relu(W1 x)^2."""
    if not cfg.expert_gate:
        h = layers.square(layers.relu(_proj(x, width, f"{p}_w1")))
        return _proj(h, cfg.hidden_size, f"{p}_w2")
    h = layers.elementwise_mul(layers.swish(_proj(x, width, f"{p}_w1")),
                               _proj(x, width, f"{p}_w3"))
    return _proj(h, cfg.hidden_size, f"{p}_w2")


def _experts(x, cfg, p, routers):
    """The routed share of the layer whose parameters start with ``p``
    [beside the shared expert]; a router with a selection bias adds its
    (name scope, bias, counts) to ``routers``."""
    shared = _feed_forward(x, cfg, cfg.shared_width, f"{p}_shared") \
        if cfg.shared_width else None
    if cfg.shared_gate:
        shared = layers.elementwise_mul(
            shared, layers.sigmoid(_proj(x, 1, f"{p}_shared_gate_w")))
    out = layers.moe_experts(
        x, cfg.num_routed, cfg.experts_held, cfg.expert_width,
        cfg.experts_per_token, expert_offset=cfg.expert_offset,
        norm_topk=cfg.norm_topk, name=p, param_attr=_attr(None),
        score=cfg.router_score, select_bias=bool(cfg.route_bias_coeff),
        norm_eps=cfg.route_norm_eps, route_scale=cfg.route_scale,
        gated=cfg.expert_gate)
    if cfg.route_bias_coeff:
        out, bias, counts = out
        routers.append((fluid.framework.current_name_scope(), bias, counts))
    return out if shared is None else layers.elementwise_add(shared, out)


def _sub_block(stream, cfg, make):
    """``(s_{j-2}, s_{j-1}) -> (s_{j-1}, s_j)`` with ``s_j = s_{j-1} +
    make(the stream the residual rule reads)``."""
    before, h = stream
    y = make(before if cfg.residual == "farskip" else h)
    return h, layers.elementwise_add(h, y)


def _block(stream, cfg, seq_len, p, scope, mixer, window, dense, routers,
           parts="both"):
    """One layer's two sub-blocks, under ``<scope>.mixer`` and
    ``<scope>.ffn``, or the one that ``parts`` names; its parameters start
    with ``p``."""
    def mix(x):
        if mixer == "conv":
            y = _short_conv(_norm(x, cfg, f"{p}_conv_norm"), cfg, p)
        elif mixer == "latent":
            y = _latent_attention(_norm(x, cfg, f"{p}_attn_norm"), cfg,
                                  seq_len, p)
        elif mixer == "delta":
            y = _delta_mixer(_norm(x, cfg, f"{p}_attn_norm"), cfg, seq_len,
                             p)
        elif mixer == "ssm":
            y = _ssm_mixer(_norm(x, cfg, f"{p}_ssm_norm"), cfg, seq_len, p)
        else:
            with fluid.name_scope("global") if own \
                    else contextlib.nullcontext():
                y = _attention(_norm(x, cfg, f"{p}_attn_norm"), cfg,
                               seq_len, p, window, own)
        return _norm(y, cfg, f"{p}_post_attn_norm") if cfg.post_norms else y

    def feed(x):
        if dense:
            f = _feed_forward(_norm(x, cfg, f"{p}_mlp_norm"), cfg,
                              cfg.dense_width, f"{p}_mlp")
        else:
            f = _experts(_norm(x, cfg, f"{p}_moe_norm"), cfg, p, routers)
        return _norm(f, cfg, f"{p}_post_mlp_norm") if cfg.post_norms else f

    # a global plain-attention layer's own rotary, where the model states
    # one: told from the window layers in the device trace by ``global``
    own = cfg.global_rotary if mixer == "attention" and not window else None
    observe.registry().inc("models.decoder.blocks", labels={
        "mixer": mixer or "none", "residual": cfg.residual,
        "where": "mtp" if scope == "mtp" else "trunk",
        **({} if parts == "both" else {"parts": parts})})
    if mixer == "delta":
        observe.registry().inc("models.decoder.delta", labels={
            "decay": "channel" if cfg.delta_gates.decay_rank else "scalar",
            "gate": cfg.delta_gates.gate})
    if mixer == "latent":
        observe.registry().inc("models.decoder.latent", labels={
            "rotary": str(int(cfg.latent.rotary)),
            "head_norm": str(int(cfg.latent.head_norm)),
            "value": str(cfg.latent.value)})
    if own:
        observe.registry().inc("models.decoder.rotary", labels={
            "kind": "global", "table": "given", "scope": scope})
    if parts != "ffn":
        with fluid.name_scope(f"{scope}.mixer"):
            stream = _sub_block(stream, cfg, mix)
    if parts == "mixer":
        return stream
    with fluid.name_scope(f"{scope}.ffn"):
        return _sub_block(stream, cfg, feed)


def _head_loss(h, cfg, norm_name, labels, weights=None):
    """(logits, mean cross-entropy) of the one head on ``h``; with
    ``weights``, one a row, each row's cross-entropy times its weight in
    the mean over all rows."""
    h = _norm(h, cfg, norm_name)
    if cfg.tie_head:
        logits = layers.matmul(
            h, fluid.default_main_program().global_block().var("tok_emb"),
            transpose_y=True)
    else:
        logits = _proj(h, cfg.vocab_size, "lm_head_w")
    rows = layers.softmax_with_cross_entropy(logits, labels)
    return logits, layers.mean(rows) if weights is None \
        else layers.weighted_mean(rows, weights)


def _embed(ids, cfg):
    h = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden_size],
                         param_attr=_attr("tok_emb"))
    if cfg.embed_scale != 1.0:
        h = layers.scale(h, scale=float(cfg.embed_scale))
    return h


def _multi_token(h, loss, cfg, seq_len, labels, routers):
    """``loss`` with the module's share added: the trunk's last stream
    merged with the NEXT token's embedding (``labels``, through the same
    table), one more routed block, the same head, against ``labels2``: the
    token after the next."""
    labels2 = layers.data(name="labels2", shape=[seq_len, 1], dtype="int64")
    with fluid.name_scope("mtp.merge"):
        merged = layers.concat([_norm(h, cfg, "mtp_h_norm"),
                                _norm(_embed(labels, cfg), cfg,
                                      "mtp_e_norm")], axis=-1)
        # the stream stays float32 under keep-low AMP, as the trunk's does
        u = layers.cast(_proj(merged, cfg.hidden_size, "mtp_merge_w"),
                        "float32")
    _, u = _block((u, u), cfg, seq_len, "mtp", "mtp", cfg.mtp_mixer(), 0,
                  False, routers)
    with fluid.name_scope("mtp.head"):
        more = _head_loss(u, cfg, "mtp_norm", labels2)[1]
        return layers.elementwise_add(
            loss, layers.scale(more, scale=float(cfg.mtp_weight)))


def _forward(cfg, seq_len):
    """Named for the device trace (``fluid.name_scope``): ``embed``,
    ``layer<i>.mixer`` (the attention of any kind with its indexer, the
    short convolution, the delta rule or the state-space scan, with
    projections, norms, gate and the residual add; what only a latent mixer
    has beneath it as ``.latent``, what only a delta mixer has as ``.delta``
    (the pairs of products of a decay or a gate rank beneath that as
    ``.gates``), what only an ssm mixer has as ``.ssm``, and all
    of a global attention layer that rotates by ``global_rotary`` but the
    residual add as ``.global``),
    ``layer<i>.ffn`` (dense or shared feed-forward, router and routed
    experts, likewise; a layer of one sub-block has that one's scope and
    not the other's), ``head`` (final norm, product, loss) and, for the
    multi-token module, ``mtp.merge``, ``mtp.mixer``, ``mtp.ffn``,
    ``mtp.head``."""
    tokens = layers.data(name="tokens", shape=[seq_len], dtype="int64")
    diffusion, weights, rows = cfg.block_diffusion, None, seq_len
    if diffusion:
        if seq_len % diffusion.block:
            raise ValueError(f"{seq_len} tokens are no whole number of "
                             f"blocks of {diffusion.block}")
        # the second data layer is the noised copy, and the trunk walks
        # both copies side by side
        second = layers.data(name="noised", shape=[seq_len], dtype="int64")
        weights = layers.data(name="weights", shape=[seq_len],
                              dtype="float32")
        rows = 2 * seq_len
    else:
        second = labels = layers.data(name="labels", shape=[seq_len, 1],
                                      dtype="int64")
    with fluid.name_scope("embed"):
        h = _embed(layers.concat([tokens, second], axis=1) if diffusion
                   else tokens, cfg)
    routers = []
    stream = (h, h)
    for i in range(cfg.num_layers):
        stream = _block(stream, cfg, rows, f"l{i}", f"layer{i}",
                        cfg.layer_mixer(i), cfg.layer_window(i),
                        cfg.layer_is_dense(i), routers, cfg.layer_parts(i))
    with fluid.name_scope("head"):
        last = stream[1]
        if diffusion:
            # the head reads the noised rows alone, and a row's label is
            # its own clean token
            last = layers.slice(last, axes=[1], starts=[seq_len],
                                ends=[rows])
            labels = layers.reshape(tokens, [-1, seq_len, 1])
        logits, loss = _head_loss(last, cfg, "final_norm", labels, weights)
    if cfg.mtp_depth:
        loss = _multi_token(stream[1], loss, cfg, seq_len, labels, routers)
    return tokens, second, loss, logits, routers


def noise(cfg, tokens, rng, floor=1e-3):
    """(noised, weights) of one step of diffusion over blocks for ``tokens``
    [B, L] (numpy, no id of it the mask's): every block of
    ``cfg.block_diffusion.block`` tokens draws a level t uniform on
    [``floor``, 1] from ``rng`` (a ``numpy.random.RandomState``), each of
    its tokens is replaced by the mask id with probability t, and a masked
    token weighs 1 / t, any other 0.  The data pipeline's half of the step:
    the program reads what this returns."""
    import numpy as np

    block, mask_id = cfg.block_diffusion
    b, length = tokens.shape
    level = np.repeat(rng.uniform(floor, 1.0, size=(b, length // block)),
                      block, axis=1)
    masked = rng.uniform(size=(b, length)) < level
    return (np.where(masked, mask_id, tokens).astype(np.int64),
            (masked / level).astype(np.float32))


def forward(cfg, seq_len):
    """Data layers, logits and the loss.  Returns (tokens, labels, loss,
    logits): the mean next-token cross-entropy, ``labels[b, t]`` the token
    that follows ``tokens[b, t]``.  With the multi-token module the program
    also reads ``labels2`` (the token after that) and ``loss`` holds the
    module's share.  Under ``cfg.block_diffusion`` the second data layer is
    ``noised`` (the noised copy of ``tokens``), the program also reads
    ``weights`` (both from ``noise``), ``logits`` are the noised rows'
    [B, seq_len, vocabulary] and ``loss`` is the weighted cross-entropy of
    the masked tokens over all ``B * seq_len``."""
    return _forward(cfg, seq_len)[:4]


def build(cfg=None, seq_len=64, lr=1e-4, beta1=0.9, beta2=0.95,
          epsilon=1e-8):
    """The training graph with Adam and, after it, the balancing rule of
    every router that has a selection bias.  Returns (tokens, labels,
    loss); under ``cfg.block_diffusion`` (tokens, noised, loss)."""
    cfg = cfg or tiny_config()
    tokens, labels, loss, _, routers = _forward(cfg, seq_len)
    fluid.optimizer.Adam(learning_rate=lr, beta1=beta1, beta2=beta2,
                         epsilon=epsilon).minimize(loss)
    for scope, bias, counts in routers:
        with fluid.name_scope(scope):
            layers.moe_bias_update(bias, counts, cfg.route_bias_coeff)
    return tokens, labels, loss
