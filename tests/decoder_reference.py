"""How a decoder test runs what it compares against, decided once: a
configuration's plain float32 reference (``chipbench/configs/<name>/
reference.py``) is traced and compiled under ``jax.jit``, once for each
``(reference, sizes)``, and what it gives for one ``(weights, feed)`` is
computed once and shared by every case that seeds the same (the ``[xla]`` and
``[pallas]`` cases of a whole-program test do).  Called bare, a reference
dispatches primitive by primitive and compiles each at each shape: most of a
case's time (docs/COVERAGE.md, "How a test runs what it compares against").

No test file: the decoder test files (``test_decoder_lm*.py``) import this
and none of them another, so a worker runs the module-level work of the file
it was handed and no other's.  ``tools/repo_lint.py`` keeps a reference's
``loss_and_grads`` from being called anywhere in ``tests/`` but here."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.parallel.ring_attention import full_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import plugins  # noqa: E402


def load(config):
    """(build, reference, sizes) of ``chipbench/configs/<config>``:
    ``sizes(**over)`` is the configuration's file with its ``tiny`` sizes
    laid over it."""
    def sizes(**over):
        read = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                           config, "config.json")))
        return {**read, **read["tiny"], **over}

    return (plugins.load(f"configs/{config}", "build"),
            plugins.load(f"configs/{config}", "reference"), sizes)


def counters(prefix):
    return {k: v for k, v in fluid.profiler.counters().items()
            if k.startswith(prefix)}


def moe_weights(rng, n, d, f, routed):
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, routed), jnp.float32)
    w1, w3 = (jnp.asarray(0.3 * rng.randn(routed, d, f), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.3 * rng.randn(routed, f, d), jnp.float32)
    return x, wr, w1, w3, w2


def dense(q, k, v, sel):
    """Grouped-query causal attention in dense float32, under a selection
    [B, T, T] where there is one."""
    g = q.shape[1] // k.shape[1]
    t = q.shape[2]
    bias = None
    if sel is not None:
        bias = jnp.where(sel > 0, 0.0, -jnp.inf)[:, None]
    kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    if bias is None:
        return full_attention(q, kr, vr, causal=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * q.shape[-1] ** -0.5 + bias
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr)


def seeded_program(build, ref, sizes, seed=5):
    """(built, names, weights): the program with the reference's weights
    from ``seed`` in the scope."""
    built = build.build(fluid, sizes)
    names = build.trainable_names(fluid.default_main_program())
    spec = ref.param_spec(sizes)
    assert [n for n, _, _ in spec] == names
    fluid.Executor(fluid.TPUPlace()).run(fluid.default_startup_program())
    scope = fluid.global_scope()
    weights = ref.init_params(seed, sizes)
    for (_, shape, _), name, w in zip(spec, names, weights):
        assert tuple(np.shape(scope.get(name))) == tuple(shape), name
        scope.set(name, jnp.array(w))
    return built, names, weights


_COMPILED = {}      # (reference's file, function, sizes) -> the jitted call
_STEPS = {}         # (reference's file, sizes, operands' digest) -> result


def compiled(ref, name, sizes):
    """``lambda *operands: ref.<name>(*operands, sizes)`` under ``jax.jit``,
    made once for each reference, function and sizes: ``optimizer_step(w,
    g)``, ``biases_after_step(weights, feed)``."""
    key = (ref.__file__, name, json.dumps(sizes, sort_keys=True))
    if key not in _COMPILED:
        fn = getattr(ref, name)
        _COMPILED[key] = jax.jit(lambda *operands: fn(*operands, sizes))
    return _COMPILED[key]


def reference_step(ref, sizes, weights, feed):
    """(loss, gradients, every router's bias after its rule) of the
    reference on ``(weights, feed)``: one compiled call, made once for each
    reference, sizes and operands.  The biases are ``None`` where the
    reference has no balancing rule."""
    def step(w, f):
        return ref.loss_and_grads(w, f, sizes) + (
            ref.biases_after_step(w, f, sizes)
            if hasattr(ref, "biases_after_step") else None,)

    digest = hashlib.sha256()
    for a in list(weights) + [feed[k] for k in sorted(feed)]:
        digest.update(np.asarray(a).tobytes())
    key = (ref.__file__, json.dumps(sizes, sort_keys=True),
           digest.hexdigest())
    if key not in _STEPS:
        _STEPS[key] = jax.jit(step)(list(weights), feed)
    return _STEPS[key]
