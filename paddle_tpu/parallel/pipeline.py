"""Pipeline parallelism: GPipe microbatch schedule over a "pp" mesh axis.

A capability the reference lacks in Fluid (SURVEY.md §2.6: PP "Absent in
Fluid"; its closest relative is the v2-era ParallelNeuralNetwork layer
pipelining, ref legacy/gserver/gradientmachines/ParallelNeuralNetwork.h:34,
which dispatches layers to devices with host threads).  The TPU formulation
is collective-based and compiles to one XLA program: stage parameters are
stacked on a leading dim sharded over "pp" (one stage per device), and
microbatches flow through the stages with one `lax.ppermute` hop per step —
activations ride ICI, the host never touches them.

Schedule: plain GPipe — M microbatches drain through S stages in
M + S - 1 steps; the bubble fraction is (S-1)/(M+S-1).  The whole schedule
is a `lax.scan`, so the backward pass is the reverse schedule for free
(ppermute/scan are differentiable) — no hand-written 1F1B needed for
correctness; XLA overlaps the ppermute with the next step's stage compute.

Composes with data parallelism: if the mesh also has a "dp" axis the batch
dim shards over it and each dp row runs an independent pipeline.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_body(params, x, stage_fn, pp_axis, n_micro):
    """Runs inside shard_map: params carry a leading stage dim of local
    size 1; x is this dp-row's LOCAL batch [N, ...]."""
    params = jax.tree_util.tree_map(lambda p: p[0], params)
    s_total = lax.axis_size(pp_axis)
    stage = lax.axis_index(pp_axis)
    n = x.shape[0]
    mb = n // n_micro
    xmb = x.reshape((n_micro, mb) + x.shape[1:])
    perm = [(j, (j + 1) % s_total) for j in range(s_total)]

    def step(carry, t):
        cur, out_buf = carry
        recv = lax.ppermute(cur, pp_axis, perm)
        in_idx = jnp.clip(t, 0, n_micro - 1)
        my_in = jnp.where(stage == 0,
                          lax.dynamic_index_in_dim(xmb, in_idx, 0,
                                                   keepdims=False),
                          recv)
        out = stage_fn(params, my_in)
        # last stage finished microbatch t-(S-1) at step t
        o_idx = jnp.clip(t - (s_total - 1), 0, n_micro - 1)
        write = (stage == s_total - 1) & (t >= s_total - 1) \
            & (t - (s_total - 1) < n_micro)
        out_buf = jnp.where(
            write,
            lax.dynamic_update_index_in_dim(out_buf, out, o_idx, 0),
            out_buf)
        return (out, out_buf), None

    # initial carries must be marked varying over the pp axis (the loop
    # writes per-stage values into them) or scan rejects the carry types;
    # zeros_like(xmb) inherits x's batch-axis vma, pcast adds pp
    cur0 = lax.pcast(jnp.zeros_like(xmb[0]), (pp_axis,), to="varying")
    buf0 = lax.pcast(jnp.zeros_like(xmb), (pp_axis,), to="varying")
    (_, out_buf), _ = lax.scan(step, (cur0, buf0),
                               jnp.arange(n_micro + s_total - 1))
    # only the last stage holds real results; psum replicates them across pp
    out_buf = lax.psum(
        jnp.where(stage == s_total - 1, out_buf, jnp.zeros_like(out_buf)),
        pp_axis)
    return out_buf.reshape((n,) + x.shape[1:])


def gpipe(stage_fn, stage_params, x, mesh: Mesh, pp_axis: str = "pp",
          n_microbatches: int = 4):
    """Run ``x`` through S pipeline stages of ``stage_fn``.

    stage_fn(params_slice, x_mb) -> y_mb must preserve the microbatch
    shape (homogeneous stages — the transformer/MLP-stack case).
    stage_params: pytree whose leaves have leading dim S = mesh.shape[pp].
    x: [N, ...] with N divisible by n_microbatches (per dp shard).
    """
    s = mesh.shape[pp_axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != s:
            raise ValueError(
                f"stage param leading dim {leaf.shape[0]} != pp size {s}")
    b_axis = "dp" if "dp" in mesh.axis_names else None
    xspec = P(*([b_axis] + [None] * (x.ndim - 1)))
    pspec = jax.tree_util.tree_map(
        lambda p: P(*([pp_axis] + [None] * (p.ndim - 1))), stage_params)
    fn = _shard_map(
        partial(_pipeline_body, stage_fn=stage_fn, pp_axis=pp_axis,
                n_micro=n_microbatches),
        mesh=mesh, in_specs=(pspec, xspec), out_specs=xspec)
    return fn(stage_params, x)


def mlp_stage_fn(act: str):
    """Stage function for a stack of equal-width fc layers: params =
    (w [L/S, D, D], b [L/S, D])."""
    def fn(params, x):
        ws, bs = params
        for i in range(ws.shape[0]):
            h = x @ ws[i] + bs[i]
            x = _apply_act(h, act)
        return x
    return fn


def _apply_act(h, act: str):
    if act == "relu":
        return jax.nn.relu(h)
    if act == "tanh":
        return jnp.tanh(h)
    if act == "gelu":
        return jax.nn.gelu(h)
    if act in (None, "", "none", "linear"):
        return h
    raise ValueError(f"unsupported pipeline activation {act!r}")


def sequential_stack(w, b, x, act: str):
    """Single-device oracle/fallback: apply all L layers in order."""
    for i in range(w.shape[0]):
        x = _apply_act(x @ w[i] + b[i], act)
    return x
