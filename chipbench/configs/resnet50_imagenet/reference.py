"""ResNet-50 in plain float32 ``jax.numpy``: forward, loss, gradients and
one momentum step, after He et al. 2015 (Table 1; bottleneck blocks with the
stride in the first 1x1 convolution, projection shortcuts where the shape
changes, batch norm after every convolution, in TRAINING mode: statistics
of the batch).  Independent of ``paddle_tpu``.

Weights come from the seed.  ``matmul_dtype`` rounds the inputs of every
convolution and of the classifier to a narrower type: the CONTROL.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BN_EPS = 1e-5
LAST_BN_SCALE = 0.1


def _convs(s):
    """(cin, cout, k, stride, bn_scale) of every convolution in creation
    order; each is followed by a batch norm.  Within a block: shortcut (if
    any), then the three convolutions.  ``bn_scale`` is the centre of that
    batch norm's initial scale: the last one of a residual branch starts
    small (Goyal et al. 2017 start it at 0), which keeps fifty layers of
    batch norm at random weights well conditioned; with every scale near 1
    the gradients of float32 and float64 runs of this very file differ by
    3%, and no comparison of precisions can be read through that."""
    out = [(3, s["stem_width"], 7, 2, 1.0)]
    cin = s["stem_width"]
    exp = s["bottleneck_expansion"]
    for stage, (n, w) in enumerate(zip(s["stage_blocks"], s["stage_widths"])):
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            if cin != w * exp or stride != 1:
                out.append((cin, w * exp, 1, stride, 1.0))
            out += [(cin, w, 1, stride, 1.0), (w, w, 3, 1, 1.0),
                    (w, w * exp, 1, 1, LAST_BN_SCALE)]
            cin = w * exp
    return out


def param_spec(s):
    spec = []
    for i, (cin, cout, k, _, scale) in enumerate(_convs(s)):
        spec.append((f"conv{i}_w", (cout, cin, k, k), ("msra",)))
        spec.append((f"bn{i}_scale", (cout,), ("near", scale)))
        spec.append((f"bn{i}_bias", (cout,), ("near", 0.0)))
    feat = s["stage_widths"][-1] * s["bottleneck_expansion"]
    spec.append(("fc_w", (feat, s["num_classes"]), ("xavier",)))
    spec.append(("fc_b", (s["num_classes"],), ("near", 0.0)))
    return spec


def init_params(seed, s):
    spec = param_spec(s)

    def make(key):
        out = []
        for i, (_, shape, init) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if init[0] == "msra":
                std = math.sqrt(2.0 / (shape[1] * shape[2] * shape[3]))
                w = std * jax.random.normal(k, shape, jnp.float32)
            elif init[0] == "xavier":
                lim = math.sqrt(6.0 / (shape[0] + shape[1]))
                w = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
            else:
                w = init[1] + jax.random.uniform(k, shape, jnp.float32,
                                                 -0.05, 0.05)
            out.append(w)
        return out

    return jax.jit(make)(jax.random.PRNGKey(np.uint32(seed % (2 ** 32))))


def loss_fn(params, feed, s, matmul_dtype=None):
    it = iter(params)

    def q(a):
        return a if matmul_dtype is None else \
            a.astype(matmul_dtype).astype(jnp.float32)

    def conv_bn(x, k, stride, relu=True):
        w, g, b = next(it), next(it), next(it)
        pad = (k - 1) // 2
        y = lax.conv_general_dilated(
            q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        mu = y.mean((0, 2, 3), keepdims=True)
        var = ((y - mu) ** 2).mean((0, 2, 3), keepdims=True)
        y = (y - mu) / jnp.sqrt(var + BN_EPS) * g[None, :, None, None] \
            + b[None, :, None, None]
        return jax.nn.relu(y) if relu else y

    x = conv_bn(feed["img"], 7, 2)
    x = lax.reduce_window(x, np.float32(-np.inf), lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    cin = s["stem_width"]
    exp = s["bottleneck_expansion"]
    for stage, (n, w) in enumerate(zip(s["stage_blocks"], s["stage_widths"])):
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            short = x
            if cin != w * exp or stride != 1:
                short = conv_bn(x, 1, stride, relu=False)
            y = conv_bn(x, 1, stride)
            y = conv_bn(y, 3, 1)
            y = conv_bn(y, 1, 1, relu=False)
            x = jax.nn.relu(short + y)
            cin = w * exp
    x = x.mean((2, 3))
    w, b = next(it), next(it)
    logp = jax.nn.log_softmax(jnp.matmul(q(x), q(w)) + b, axis=-1)
    picked = jnp.take_along_axis(logp, feed["label"], axis=-1)
    return -picked.mean()


def loss_and_grads(params, feed, s, matmul_dtype=None):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(list(params), feed, s,
                                           matmul_dtype)


def optimizer_step(param, grad, s):
    """The first momentum step from zero velocity: v = g, p -= lr * v."""
    return param - s["optimizer"]["lr"] * grad


def step_size(s):
    return s["optimizer"]["lr"]
