"""The comparison that decides ``correct``.

One deterministic training step of the program on a small seeded batch is
set against the configuration's plain float32 reference, which makes its
own weights from the seed.  Two numbers decide, each printed beside its
limit:

- ``grad_rel``: the relative L2 error of ALL gradients taken as one vector
  (forward, loss and backward: a fault anywhere upstream of a parameter
  reaches its gradient);
- ``update_rel``: the largest |parameter after the step - the reference
  optimizer's first step applied to the program's own gradient|, in units
  of the learning rate: the optimizer sweep alone, whatever the gradients'
  precision.

``loss_rel`` (|loss - reference loss| / |reference loss|) is printed beside
them and decides nothing: on the chip the float8 control reads under three
times what the program reads (the loss averages rounding away), so no limit
on it would hold.  The largest error of a single parameter's gradient was
tried and dropped for the same reason: tensors whose true gradient is
nearly zero read 100-300% in sound runs.

``control`` puts the reference, computed with narrower contraction inputs,
in the program's place: the comparison has to call it NOT correct.  The
limits live in the configuration's ``config.json`` under ``limits``, with
the readings they were set from in ``PERF.md``.
"""

from __future__ import annotations

import functools

KEYS = ("grad_rel", "update_rel")


def _errors(loss, grads, ref_loss, ref_grads):
    import jax.numpy as jnp

    num = den = 0.0
    for g, r in zip(grads, ref_grads):
        g = jnp.asarray(g, jnp.float32).reshape(r.shape)
        num, den = num + jnp.sum((g - r) ** 2), den + jnp.sum(r ** 2)
    loss = jnp.asarray(loss, jnp.float32).reshape(())
    return {"loss_rel": jnp.abs(loss - ref_loss) / jnp.abs(ref_loss),
            "grad_rel": jnp.sqrt(num / den), "reference_loss": ref_loss}


@functools.lru_cache(maxsize=None)
def _jitted(reference, sizes_key, matmul_dtype):
    """One jitted comparison per (reference, sizes, control type): the
    reference's loss and gradients, the errors, and the optimizer step."""
    import json

    import jax
    import jax.numpy as jnp

    sizes = json.loads(sizes_key)
    step = reference.step_size(sizes)

    def against_program(weights, feed, loss, grads, params_after):
        ref_loss, ref_grads = reference.loss_and_grads(weights, feed, sizes)
        out = _errors(loss, grads, ref_loss, ref_grads)
        upd = 0.0
        for w, g, p in zip(weights, grads, params_after):
            want = reference.optimizer_step(
                w, jnp.asarray(g, jnp.float32).reshape(w.shape), sizes)
            upd = jnp.maximum(upd, jnp.max(jnp.abs(
                jnp.asarray(p, jnp.float32).reshape(w.shape) - want)) / step)
        return {**out, "update_rel": upd}

    def against_control(weights, feed):
        ref_loss, ref_grads = reference.loss_and_grads(weights, feed, sizes)
        loss, grads = reference.loss_and_grads(weights, feed, sizes,
                                               matmul_dtype)
        out = _errors(loss, grads, ref_loss, ref_grads)
        upd = 0.0
        for w, g in zip(weights, grads):
            want = reference.optimizer_step(w, g, sizes)
            low = want.astype(jnp.bfloat16).astype(jnp.float32)
            upd = jnp.maximum(upd, jnp.max(jnp.abs(low - want)) / step)
        return {**out, "update_rel": upd}

    return jax.jit(against_program if matmul_dtype is None
                   else against_control)


def _call(reference, sizes, matmul_dtype, *args):
    import json

    import jax

    fn = _jitted(reference, json.dumps(sizes, sort_keys=True), matmul_dtype)
    return {k: float(v) for k, v in jax.device_get(fn(*args)).items()}


def program(reference, sizes, weights, feed, loss, grads, params_after):
    """The program's step against the reference.  ``loss``, ``grads`` and
    ``params_after`` are taken as they come: device arrays stay on the
    device, so the comparison holds no second copy of them (how many copies
    of the parameters it does hold: README, "Sizing the comparison")."""
    import numpy as np

    return _call(reference, sizes, None, list(weights),
                 {k: np.asarray(v) for k, v in feed.items()},
                 loss, list(grads), list(params_after))


def control(reference, sizes, weights, feed, matmul_dtype):
    """The reference at ``matmul_dtype`` in the program's place.  Its
    optimizer step is the reference's own, rounded to bfloat16."""
    import numpy as np

    return _call(reference, sizes, matmul_dtype, list(weights),
                 {k: np.asarray(v) for k, v in feed.items()})


def decide(numbers: dict, limits: dict) -> bool:
    """Every compared number is finite and within its limit.  A number
    without a limit in the configuration's file fails: no guessed limits."""
    ok = True
    for k in KEYS:
        v, lim = numbers.get(k), limits.get(k)
        ok = ok and v is not None and lim is not None and v == v \
            and v <= lim
    return ok


def report(numbers: dict, limits: dict):
    for k in KEYS:
        lim = limits.get(k)
        v = numbers.get(k)
        mark = "ok" if (lim is not None and v is not None and v <= lim) \
            else "NOT WITHIN"
        yield (f"compare {k}: {v!r} limit {lim!r} {mark}")
    yield (f"not compared: loss_rel {numbers.get('loss_rel')!r}, "
           f"reference_loss {numbers.get('reference_loss')!r}")
