"""The grouped-product kernels (ops/pallas_grouped.py) in interpret mode
against ``lax.ragged_dot`` / ``lax.ragged_dot_general``, and the expert
layer's choice between them and XLA's grouped product by shape
(parallel/moe.py ``grouped_product``).  What Mosaic refuses, and the
cells' own shapes, are ``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, layers
from paddle_tpu.ops import pallas_grouped as pg
from paddle_tpu.parallel import moe

M, K, N = 1024, 128, 256          # two row tiles of 512, lane-aligned widths
#: group sizes over M rows: what each case is there for
SIZES = {
    "uneven": [100, 412, 300, 212],           # boundaries inside both tiles
    "empty_group": [512, 0, 212, 300],        # and one on a tile's edge
    "three_groups_in_a_tile": [40, 30, 20, 934],
    "all_rows_in_the_last": [0, 0, 0, 1024],  # as the cells' absent rows lie
    "short": [100, 0, 156, 200],              # 456 < M: a tile of no group
    "no_rows": [0, 0, 0, 0],
}
FORMS = ("plain", "transposed", "weights_gradient")


def operands(dtype, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(M, K), dtype),
            jnp.asarray(rng.randn(M, N), dtype),
            jnp.asarray(rng.randn(len(SIZES["uneven"]), K, N), dtype))


def poisoned(a, total):
    return a.at[total:].set(jnp.nan)


def weights_gradient(rows, cot, sizes):
    """[G, K, N] by ``lax.ragged_dot_general`` with the ROWS the ragged,
    contracted dimension of both operands."""
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return lax.ragged_dot_general(rows, cot, sizes, dims)


def assert_equals_xlas_product(form, rows, other, case, dtype):
    """One form over ``rows`` and its ``other`` operand (the weights, or
    for the weights' gradient the cotangent) in the groups of ``case``; the
    rows past the last group hold NaN in the operands and must come back
    as zeros (products) or add nothing (the weights' gradient)."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    total = sum(SIZES[case])
    zeroed = lambda a: a.at[total:].set(0)      # noqa: E731
    if form == "plain":
        got = pg.grouped_matmul(poisoned(rows, total), other, sizes)
        want = zeroed(lax.ragged_dot(rows, other, sizes))
    elif form == "transposed":
        got = pg.grouped_matmul(poisoned(rows, total), other, sizes,
                                transpose=True)
        want = zeroed(lax.ragged_dot(rows, jnp.swapaxes(other, 1, 2),
                                     sizes))
    else:
        got = pg.grouped_matmul_t(poisoned(rows, total),
                                  poisoned(other, total), sizes)
        want = weights_gradient(zeroed(rows), zeroed(other), sizes)
    assert got.dtype == dtype and got.shape == want.shape
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, want,
                               atol=tol * max(np.abs(want).max(), 1.0))
    if form != "weights_gradient":
        assert not got[total:].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
@pytest.mark.parametrize("form", FORMS)
def test_kernel_equals_xlas_grouped_product(form, case, dtype):
    """Every form at every arrangement of groups."""
    rows, cot, w = operands(dtype)
    assert_equals_xlas_product(
        form, *{"plain": (rows, w), "transposed": (cot, w),
                "weights_gradient": (rows, cot)}[form], case, dtype)


@pytest.mark.parametrize("empty_groups", [False, True])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_visits_name_every_tile_of_every_group_once(case, empty_groups):
    """The scalar-prefetch table: int32, ``tiles + G - 1`` steps, each
    (row tile, group) with rows in common exactly once and in order; for
    the products every tile past the last group once more (written as
    zeros), for the weights' gradient every group without rows once (so
    written as zeros); what is left repeats the last step."""
    tm = pg.ROW_TILE
    sizes = SIZES[case]
    offsets, tiles, groups = pg.visits(jnp.asarray(sizes, jnp.int32), M, tm,
                                       empty_groups)
    assert all(a.dtype == jnp.int32 for a in (offsets, tiles, groups))
    assert offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    steps = list(zip(tiles.tolist(), groups.tolist()))
    assert len(steps) == M // tm + len(sizes) - 1
    seen = [s for i, s in enumerate(steps) if i == 0 or s != steps[i - 1]]
    assert len(set(seen)) == len(seen) and seen == sorted(
        seen, key=lambda s: (s[1], s[0]) if empty_groups else s)
    lo = np.cumsum([0] + sizes)
    want = {(t, g) for g, n in enumerate(sizes) for t in range(M // tm)
            if n and lo[g] < (t + 1) * tm and lo[g + 1] > t * tm}
    live = {(t, g) for t, g in seen
            if sizes[g] and lo[g] < (t + 1) * tm and lo[g + 1] > t * tm}
    assert live == want
    if empty_groups:
        assert {g for _, g in seen} == set(range(len(sizes)))
    else:
        assert {t for t, _ in seen} == set(range(M // tm))


#: the cells' products (BENCHMARK.json's four decoder cells: rows x
#: [experts held, hidden, expert width]) and the column tile each form
#: takes: the whole contraction, and of the result's width the fewest tiles
#: that fit the budget (Mosaic's default VMEM, no limit stated), ragged at
#: Instella's 1,408 = 11 x 128 (768 + 640; 384 x 3 + 256)
CELLS = {"keye": (65536, 2048, 768), "trinity": (49152, 2048, 1024),
         "lfm2": (32768, 2048, 1792), "instella": (49152, 2048, 1408),
         "qwen3_next": (81920, 2048, 512), "mellum2": (65536, 2304, 896)}
TILES = {("keye", "up"): 768, ("keye", "down"): 2048,
         ("keye", "up_t"): 384, ("keye", "down_t"): 1024,
         ("trinity", "up"): 512, ("trinity", "down"): 1024,
         ("trinity", "up_t"): 256, ("trinity", "down_t"): 512,
         ("lfm2", "up"): 896, ("lfm2", "down"): 1024,
         ("lfm2", "up_t"): 256, ("lfm2", "down_t"): 256,
         ("instella", "up"): 768, ("instella", "down"): 1024,
         ("instella", "up_t"): 384, ("instella", "down_t"): 512,
         ("qwen3_next", "up"): 512, ("qwen3_next", "down"): 2048,
         ("qwen3_next", "up_t"): 256, ("qwen3_next", "down_t"): 1024,
         # the first contraction that is not 2,048 wide
         ("mellum2", "up"): 512, ("mellum2", "down"): 1152,
         ("mellum2", "up_t"): 256, ("mellum2", "down_t"): 1152}


def vmem_bytes(tm, k, tn, n, itemsize, transposed_result):
    """What a grid step keeps in VMEM, as ``pg.tile``'s docstring counts
    it: every block that moves twice, one chunk's float32 product (and its
    cast), and for the weights' gradient the accumulator, the masked rows
    and one chunk's product."""
    width = pg._chunk(tn, n)
    blocks = 2 * itemsize * (tm * k + k * tn + tm * tn)
    if transposed_result:
        return blocks + 4 * k * tn + itemsize * tm * k + 4 * k * width
    return blocks + (4 + itemsize) * tm * width


@pytest.mark.parametrize("cell,form", sorted(TILES))
def test_tile_is_derived_from_the_shapes_and_the_budget(cell, form):
    m, d, f = CELLS[cell]
    k, n = (d, f) if form.startswith("up") else (f, d)
    transposed = form.endswith("_t")
    tm, tn = pg.tile(m, k, n, 2, transposed_result=transposed)
    assert (tm, tn) == (512, TILES[cell, form])
    assert tn % pg.LANE == 0
    assert vmem_bytes(tm, k, tn, n, 2, transposed) <= pg.VMEM_BUDGET \
        < 16 << 20
    # one column tile fewer does not fit the budget; the weights' gradient
    # may stay at a tile that divides the width instead, of two lane rows
    # or more
    tiles = -(-n // tn)
    if tiles > 1 and not (transposed and n % tn == 0 and tn > pg.LANE):
        wider = pg.LANE * -(-(n // pg.LANE) // (tiles - 1))
        assert vmem_bytes(tm, k, wider, n, 2, transposed) > pg.VMEM_BUDGET
    # the chunk of one product divides the tile and what the last tile
    # holds of the width: nothing past the result's edge is multiplied
    assert tn % pg._chunk(tn, n) == 0 and (n % tn) % pg._chunk(tn, n) == 0
    # float32 operands take twice the room: never a wider tile
    assert pg.tile(m, k, n, 4, transposed)[1] <= tn


def test_the_weights_gradient_is_ragged_only_where_no_divisor_fits():
    """Trinity's and LFM2's weights' gradients would fit 384 x 3 and 384 x
    5 ragged; they keep the dividing 256 (faster on the chip, PERF.md
    Findings PR 42).  At 11, 13, 17, 19 and 23 lane rows every divisor but
    one lane row is over the budget, and the ragged tile is taken."""
    for n, want in ((1024, 256), (1792, 256), (1408, 384), (1664, 384),
                    (2176, 384), (2432, 384), (2944, 384)):
        assert pg.tile(49152, 2048, n, 2, transposed_result=True)[1] == want
    # the products take the fewest tiles whatever divides
    for n, want in ((1408, 768), (1664, 896), (2816, 768)):
        assert pg.tile(49152, 2048, n, 2)[1] == want


#: a result's width that no fitting tile divides, at shapes small enough to
#: interpret: (contraction, width, column tile).  The budget is lowered to
#: exactly what that tile takes
RAGGED = {"640_as_384_and_256": (256, 640, 384),
          # a tile of 512 alone is one chunk: the last tile holds less
          "896_as_512_and_less_than_a_chunk": (128, 896, 512),
          "1408_as_three_of_384_and_256": (128, 1408, 384)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["short", "uneven"])
@pytest.mark.parametrize("shape", sorted(RAGGED))
@pytest.mark.parametrize("form", FORMS)
def test_a_ragged_last_column_tile_equals_xlas_grouped_product(
        monkeypatch, form, shape, case, dtype):
    """The column tile does not divide the width: the last tile runs past
    the result (and past the weights or the cotangent), whatever is read
    there is dropped, and the chunk loop stops at the edge.  ``short``: an
    empty group, a boundary inside a row tile and a row tile past the last
    group (zeros), its operands NaN there."""
    c, n, tn = RAGGED[shape]
    itemsize = jnp.dtype(dtype).itemsize
    transposed = form == "weights_gradient"
    monkeypatch.setattr(pg, "VMEM_BUDGET",
                        vmem_bytes(512, c, tn, n, itemsize, transposed))
    assert pg.tile(M, c, n, itemsize, transposed) == (512, tn)
    assert n % tn and pg._chunk(tn, n) <= n % tn
    rng = np.random.RandomState(1)
    rows = jnp.asarray(rng.randn(M, c), dtype)
    other = {"plain": (len(SIZES[case]), c, n),
             "transposed": (len(SIZES[case]), n, c),
             "weights_gradient": (M, n)}[form]
    assert_equals_xlas_product(form, rows, jnp.asarray(rng.randn(*other),
                                                       dtype), case, dtype)


@pytest.mark.parametrize("rows,weights,dtype,why", [
    ((512, 128), (4, 128, 256), jnp.bfloat16, ""),
    ((512, 256), (4, 128, 256), jnp.float32, ""),      # the cotangent's
    ((48, 64), (4, 64, 32), jnp.float32, "lanes"),     # the rehearsals'
    ((500, 128), (4, 128, 256), jnp.float32, "rows"),
    ((512, 128), (4, 128, 200), jnp.float32, "lanes"),
    ((512, 384), (4, 128, 256), jnp.float32, "shape"),
    ((512, 128), (4, 128, 256), jnp.float16, "dtype"),
    ((512, 128), (128, 256), jnp.float32, "rank"),
])
def test_supported_says_why_not(rows, weights, dtype, why):
    assert pg.supported(jax.ShapeDtypeStruct(rows, dtype),
                        jax.ShapeDtypeStruct(weights, dtype)) == why


def layer_weights(rng, n, d, f, routed, held):
    return (jnp.asarray(rng.randn(n, d), jnp.float32),
            jnp.asarray(rng.randn(d, routed), jnp.float32),
            *(jnp.asarray(0.1 * rng.randn(held, *s), jnp.float32)
              for s in ((d, f), (d, f), (f, d))))


def test_routed_experts_take_the_kernels_at_lane_aligned_sizes(monkeypatch):
    """256 tokens x top-2 = 512 rows, hidden and expert width 128: all
    three products, their rows' cotangents and their weights' gradients go
    through the kernels, none through ``lax.ragged_dot``; value and every
    gradient, the router's among them, equal the ``ragged_dot`` path's."""
    n, d, f, routed, held, k = 256, 128, 128, 8, 3, 2
    x, wr, w1, w3, w2 = layer_weights(np.random.RandomState(0), n, d, f,
                                      routed, held)
    calls = {"grouped_matmul": 0, "grouped_matmul_t": 0, "ragged_dot": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    for name in ("grouped_matmul", "grouped_matmul_t"):
        monkeypatch.setattr(pg, name, counted(name, getattr(pg, name)))
    monkeypatch.setattr(moe.lax, "ragged_dot",
                        counted("ragged_dot", lax.ragged_dot))

    def program(x, wr, w1, w3, w2):
        return jnp.sum(moe.routed_experts(x, wr, w1, w3, w2, top_k=k,
                                          expert_offset=2) ** 2)

    assert moe.product_path(x, w1, w2, k) == "pallas"
    got = jax.value_and_grad(program, range(5))(x, wr, w1, w3, w2)
    # forward 3; the hand-written backward: the two hidden products again
    # (not the last one) and three rows' cotangents; gradients 3
    assert calls == {"grouped_matmul": 8, "grouped_matmul_t": 3,
                     "ragged_dot": 0}
    monkeypatch.setattr(pg, "supported", lambda *a: "off")
    assert moe.product_path(x, w1, w2, k) == "ragged_dot"
    want = jax.value_and_grad(program, range(5))(x, wr, w1, w3, w2)
    assert calls["grouped_matmul"] == 8 and calls["ragged_dot"] >= 3
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, r in zip(got[1], want[1]):
        np.testing.assert_allclose(g, r, atol=1e-4 * float(jnp.abs(r).max()))


@pytest.mark.parametrize("width,path", [(128, "pallas"), (32, "ragged_dot")])
def test_the_op_counts_the_path_it_took(width, path):
    """``ops.moe.calls{path}`` through the op, a program and the executor:
    ``pallas`` at a lane-aligned decoder size, ``ragged_dot`` at a width
    that is not (the rehearsals' and the tiny decoders' case); twice a
    layer, the generic vjp traces the forward again."""
    x = layers.data(name="x", shape=[128, width], dtype="float32")
    out = layers.moe_experts(x, num_routed=8, experts_held=4,
                             hidden_size=width, top_k=2, name="moe")
    loss = layers.mean(layers.square(out))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(1).randn(2, 128, width)
            .astype("float32")}
    value, = exe.run(framework.default_main_program(), feed=feed,
                     fetch_list=[loss])
    assert np.isfinite(value).all()
    found = {k: v for k, v in fluid.profiler.counters().items()
             if k.startswith("ops.moe.calls")}
    assert found == {f'ops.moe.calls{{held="4",path="{path}",'
                     f'routed="8"}}': 2}
