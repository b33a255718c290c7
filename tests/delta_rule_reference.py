"""What the delta rule's tests compare against, and how they run it
(``tests/test_delta_rule.py``, ``tests/test_delta_rule_kernels.py``,
``tests/test_delta_channel_kernels.py``): the rule token by token under
either decay, operands, a scalar that weighs every element of an output
differently, and both sides of a comparison compiled (``cotangents``,
``both_paths``).  No test file, so that none of the three imports another."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.ops import delta_rule


def recurrence(q, k, v, g, beta, scale, norm_eps=0.0):
    """The rule as it is stated: one token after another, one [dk, dv]
    state a value head.  q, k: [B, T, Hk, dk]; v: [B, T, Hv, dv]; g, beta:
    [B, T, Hv]."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if norm_eps:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + norm_eps)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + norm_eps)
    q = jnp.repeat(q, hv // hk, 2) * scale
    k = jnp.repeat(k, hv // hk, 2)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(token, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def operands(t, seed=0, b=2, hk=2, hv=4, dk=8, dv=12, decay=0.5):
    rng = np.random.RandomState(seed)
    k = rng.randn(b, t, hk, dk)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.randn(b, t, hk, dk), k / np.linalg.norm(k, axis=-1,
                                                    keepdims=True),
        rng.randn(b, t, hv, dv), -decay * rng.rand(b, t, hv),
        rng.rand(b, t, hv)))


def weighted_sum(fn):
    """A scalar of ``fn``'s output that weighs every element differently."""
    def loss(*xs):
        out = fn(*xs)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                                     ).reshape(out.shape))
    return loss


def cotangents(fn):
    """``jax.grad`` of ``weighted_sum(fn)`` in all five operands, compiled."""
    return jax.jit(jax.grad(weighted_sum(fn), range(5)))


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def rel(got, want):
    """The distance of two arrays as a share of the second's norm."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def eqns_of(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from eqns_of(sub)


def build_rule(t, hk=2, hv=4, dk=8, dv=12, chunk=16, channel=False,
               **attrs):
    names = ("q", "k", "v", "g", "beta")
    shapes = ([t, hk, dk], [t, hk, dk], [t, hv, dv],
              [t, hv, dk] if channel else [t, hv], [t, hv])
    data = [layers.data(name=n, shape=s, dtype="float32")
            for n, s in zip(names, shapes)]
    for d in data:
        d.stop_gradient = False
    out = layers.gated_delta_rule(*data, chunk=chunk, **attrs)
    return names, data, out


def channel_recurrence(q, k, v, g, beta, scale):
    """The rule token by token with ``g`` [B, T, H, dk]: the state's ROWS
    decay, each key channel by its own number.  q, k: [B, T, H, dk]."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, u)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t * scale)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(token, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def channel_operands(t, decay, seed=0, b=2, h=3, dk=8, dv=12):
    q, k, v, _, beta = operands(t, seed=seed, b=b, hk=h, hv=h, dk=dk, dv=dv)
    g = -decay * np.random.RandomState(seed + 1).rand(b, t, h, dk)
    return q, k, v, jnp.asarray(g, jnp.float32), beta


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def out_and_cotangents(path, low, eps, *xs):
    """(out, five cotangents) of ``chunked`` at the published chunk, one
    forward, compiled once for each ``path`` (which only keys jax's cache:
    the gate is the environment's while this is traced), AMP type, epsilon
    and operands' shapes: two cases of one shape share the program."""
    def loss(*a):       # the value beside it: one forward
        out = delta_rule.chunked(*a, chunk=64, norm_eps=eps)
        return weighted_sum(lambda: out.astype(jnp.float32))(), out

    with fluid.amp.amp_guard(low, keep_activations=True) if low \
            else contextlib.nullcontext():
        (_, out), grads = jax.value_and_grad(loss, range(5), has_aux=True)(
            *xs)
    return out, grads


def both_paths(xs, low, eps):
    """{path: (out, five cotangents)} of ``xs`` through ``chunked`` with
    the flash gate closed ('xla': the twin) and open ('pallas': the
    kernels, interpreted on this CPU)."""
    import os

    from paddle_tpu.ops import kernel_choice

    runs, name = {}, kernel_choice.SWITCHES["flash"]
    before = os.environ.get(name)
    try:
        for path, flag in (("xla", "0"), ("pallas", "1")):
            os.environ[name] = flag

            def rule(*a):       # a path its own: jax keeps a trace
                return delta_rule.chunked(*a, chunk=64, norm_eps=eps)

            with fluid.amp.amp_guard(low, keep_activations=True) \
                    if low else contextlib.nullcontext():
                jaxpr = jax.make_jaxpr(rule)(*xs)
            assert any(e.primitive.name == "pallas_call"
                       for e in eqns_of(jaxpr)) is (path == "pallas")
            runs[path] = out_and_cotangents(path, low, eps, *xs)
    finally:
        os.environ.pop(name) if before is None \
            else os.environ.__setitem__(name, before)
    return runs
