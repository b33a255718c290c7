"""Pipeline-parallelism tests (GPipe over a "pp" mesh axis).

PP is a TPU-native capability beyond the reference (SURVEY.md §2.6: PP
"Absent in Fluid"; nearest relative is v2's ParallelNeuralNetwork thread
pipelining).  Bar: exact equivalence with the sequential single-device
computation (SURVEY.md §4.4 oracle style).
"""

import numpy as np
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
import paddle_tpu.fluid.executor as _executor
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.spmd import ShardedTrainStep


def test_gpipe_matches_sequential_fwd_and_grad():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import pipeline as pl

    mesh = make_mesh(8, tp=4, axis_names=("dp", "pp"))
    rng = np.random.RandomState(0)
    s, per, d, n, m = 4, 2, 8, 16, 4
    w = jnp.asarray(rng.normal(scale=0.3, size=(s * per, d, d))
                    .astype(np.float32))
    b = jnp.asarray(rng.normal(scale=0.1, size=(s * per, d))
                    .astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

    def piped(w, b, x):
        params = (w.reshape(s, per, d, d), b.reshape(s, per, d))
        return pl.gpipe(pl.mlp_stage_fn("relu"), params, x, mesh,
                        "pp", m)

    ref = jax.jit(lambda *a: pl.sequential_stack(*a, "relu"))(w, b, x)
    out = jax.jit(piped)(w, b, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # gradients flow back through the scan/ppermute schedule
    g_pipe = jax.jit(jax.grad(lambda w, b, x: (piped(w, b, x) ** 2).sum(),
                              argnums=(0, 1)))(w, b, x)
    g_ref = jax.jit(jax.grad(
        lambda w, b, x: (pl.sequential_stack(w, b, x, "relu") ** 2).sum(),
        argnums=(0, 1)))(w, b, x)
    for gp, gr in zip(g_pipe, g_ref):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4)


def _build_pp_model(seed=9):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    img = fluid.layers.data(name="img", shape=[16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(input=img, size=32, act="relu")
    h = fluid.layers.gpipe_mlp_stack(h, n_layers=4, act="relu",
                                     n_microbatches=4)
    pred = fluid.layers.fc(input=h, size=10, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    return loss


def test_pp_program_matches_executor():
    """dp2 x pp4: stacked stage weights shard over "pp"; the GPipe schedule
    must reproduce the single-device loss curve exactly."""
    loss = _build_pp_model()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    scope = _executor._global_scope
    init = {k: np.asarray(scope.get(k)) for k in scope.keys()}

    rng = np.random.RandomState(4)
    data = []
    for _ in range(5):
        x = rng.normal(size=(16, 16)).astype(np.float32)
        data.append((x, (x[:, :1] > 0).astype(np.int64)))

    base = []
    for x, y in data:
        (l,) = exe.run(fluid.default_main_program(),
                       feed={"img": x, "label": y}, fetch_list=[loss])
        base.append(float(np.asarray(l).reshape(-1)[0]))
    assert base[-1] < base[0]

    for k, v in init.items():
        scope.set(k, v)
    mesh = make_mesh(8, tp=4, axis_names=("dp", "pp"))
    step = ShardedTrainStep(fluid.default_main_program(), ["img", "label"],
                            [loss.name], mesh)
    pp_sharded = [n for n, s in step.specs.items()
                  if s is not None and "pp" in tuple(s)]
    assert len(pp_sharded) >= 2, f"stack weights not pp-sharded: {step.specs}"

    state = step.place_state()
    out = []
    for x, y in data:
        placed = step.place_feed({"img": x, "label": y})
        fetches, new_state = step(placed, state)
        state = {**state, **new_state}
        out.append(float(np.asarray(fetches[0]).reshape(-1)[0]))
    np.testing.assert_allclose(base, out, rtol=1e-4, atol=1e-4)


def test_pp_fallback_single_device():
    """Without a pp mesh the op applies the stack sequentially."""
    loss = _build_pp_model(seed=3)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(5)
    losses = []
    for _ in range(6):
        x = rng.normal(size=(16, 16)).astype(np.float32)
        y = (x[:, :1] > 0).astype(np.int64)
        (l,) = exe.run(fluid.default_main_program(),
                       feed={"img": x, "label": y}, fetch_list=[loss])
        losses.append(float(np.asarray(l).reshape(-1)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
