"""The experts' grouped products as Pallas kernels
(``ops/pallas/grouped_matmul.py``), under the interpreter on the CPU: each
kernel and the fused gate against a float32 loop over the groups, the path
``ops/grouped_matmul.py`` takes off the TPU, and the VMEM estimate behind
the tile selector."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import grouped_matmul as entry
from analytics_zoo_tpu.ops.pallas import grouped_matmul as kernels

ROWS, D, H = 512, 256, 128

#: group sizes over 512 rows in 128-row tiles
SIZES = {
    "empty_group": (140, 0, 170, 50),
    "ends_inside_a_tile": (130, 3, 0, 123),
    "one_group": (0, 512, 0, 0),
    "short_of_the_rows": (100, 200, 50, 10),
    "all_the_rows": (128, 192, 64, 128),
    "no_rows": (0, 0, 0, 0),
}

#: one rounding to the dtype on the way out of a product, and float32's
#: summation order
BOUND = {jnp.float32: 2e-5, jnp.bfloat16: 2 * 2.0 ** -8}


def _operands(sizes, dtype, seed=7):
    rng = np.random.default_rng(seed)
    g = len(sizes)
    x = jnp.asarray(rng.normal(size=(ROWS, D)), dtype)
    wgate, wup = (jnp.asarray(rng.normal(size=(g, D, H)) / np.sqrt(D), dtype)
                  for _ in range(2))
    wdown = jnp.asarray(rng.normal(size=(g, H, D)) / np.sqrt(H), dtype)
    return x, wgate, wup, wdown, jnp.asarray(sizes, jnp.int32)


def _bounds(sizes):
    ends = np.cumsum(sizes)
    return list(zip(ends - np.asarray(sizes), ends))


def _loop(x, w, sizes, transpose=False):
    """float32, one product a group, zero rows past the groups."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    if transpose:
        w = w.swapaxes(1, 2)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for g, (lo, hi) in enumerate(_bounds(sizes)):
        out[lo:hi] = x[lo:hi] @ w[g]
    return out


def _loop_dw(x, dy, sizes):
    x, dy = np.asarray(x, np.float32), np.asarray(dy, np.float32)
    return np.stack([x[lo:hi].T @ dy[lo:hi] for lo, hi in _bounds(sizes)])


def _err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


cases = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                ids=["float32", "bfloat16"])
sizes_of = pytest.mark.parametrize("sizes", list(SIZES.values()),
                                   ids=list(SIZES))


@cases
@sizes_of
@pytest.mark.parametrize("tiles", [(128, 256, 128), (256, 128, 128)],
                         ids=["k_whole", "k_cut"])
def test_gmm_and_its_transpose_against_a_loop(sizes, dtype, tiles):
    x, wgate, _, wdown, gs = _operands(sizes, dtype)
    held = sum(sizes)
    got = kernels.gmm(x, wgate, gs, tiles=tiles, interpret=True)
    assert got.dtype == x.dtype
    assert _err(got, _loop(x, wgate, sizes)) <= BOUND[dtype]
    np.testing.assert_array_equal(np.asarray(got[held:], np.float32), 0.0)
    # dx of the down product: w (G, h, d) read transposed, no copy
    got = kernels.gmm(x, wdown, gs, transpose_rhs=True, tiles=tiles,
                      interpret=True)
    assert _err(got, _loop(x, wdown, sizes, transpose=True)) <= BOUND[dtype]
    np.testing.assert_array_equal(np.asarray(got[held:], np.float32), 0.0)


@cases
@sizes_of
@pytest.mark.parametrize("tiles", [(128, 256, 128), (256, 128, 128)],
                         ids=["k_whole", "k_cut"])
def test_gated_gmm_against_a_loop(sizes, dtype, tiles):
    x, wgate, wup, _, gs = _operands(sizes, dtype)
    held = sum(sizes)
    # as the unfused composition rounds: gate and up first, then their gate
    gate = jnp.asarray(_loop(x, wgate, sizes), dtype)
    up = jnp.asarray(_loop(x, wup, sizes), dtype)
    act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32))
    got = kernels.gated_gmm(x, wgate, wup, gs, residuals=True, tiles=tiles,
                            interpret=True)
    for a, want in zip(got, (act, gate, up)):
        assert a.dtype == x.dtype
        assert _err(a, want) <= 2 * BOUND[dtype]
        np.testing.assert_array_equal(np.asarray(a[held:], np.float32), 0.0)
    alone, = kernels.gated_gmm(x, wgate, wup, gs, tiles=tiles,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(alone, np.float32),
                                  np.asarray(got[0], np.float32))


@cases
@sizes_of
def test_gated_gmm_dx_against_autodiff_of_a_loop(sizes, dtype):
    x, wgate, wup, _, gs = _operands(sizes, dtype)
    held = sum(sizes)
    gate = jnp.asarray(_loop(x, wgate, sizes), dtype)
    up = jnp.asarray(_loop(x, wup, sizes), dtype)
    rng = np.random.default_rng(11)
    d_act = jnp.asarray(rng.normal(size=(ROWS, H)), dtype)
    _, vjp = jax.vjp(lambda g, u: (jax.nn.silu(g.astype(jnp.float32))
                                   * u.astype(jnp.float32)).astype(dtype),
                     gate, up)
    d_gate, d_up = vjp(d_act)
    live = (np.arange(ROWS) < held)[:, None]
    d_gate, d_up = (jnp.where(live, t, 0) for t in (d_gate, d_up))
    dx = (_loop(d_gate, wgate, sizes, transpose=True)
          + _loop(d_up, wup, sizes, transpose=True))
    got = kernels.gated_gmm_dx(d_act, gate, up, wgate, wup, gs,
                               tiles=(128, H, 128), interpret=True)
    for a, want in zip(got, (dx, d_gate, d_up)):
        assert a.dtype == x.dtype
        assert _err(a, want) <= 2 * BOUND[dtype]
        np.testing.assert_array_equal(np.asarray(a[held:], np.float32), 0.0)


@cases
@sizes_of
def test_gmm_dw_against_a_loop(sizes, dtype):
    x, _, _, _, gs = _operands(sizes, dtype)
    rng = np.random.default_rng(13)
    dya, dyb = (jnp.asarray(rng.normal(size=(ROWS, H)), dtype)
                for _ in range(2))
    # what stands in the rows past the groups is not the kernel's to read
    held = sum(sizes)
    x = x.at[held:].set(jnp.inf)
    got = kernels.gmm_dw(x, (dya, dyb), gs, tiles=(128, 128, 128),
                         interpret=True)
    x = x.at[held:].set(0.0)
    for a, dy in zip(got, (dya, dyb)):
        assert a.dtype == jnp.float32 and a.shape == (len(sizes), D, H)
        assert _err(a, _loop_dw(x, dy, sizes)) <= BOUND[dtype]
    for g, n in enumerate(sizes):
        if n == 0:
            np.testing.assert_array_equal(np.asarray(got[0][g]), 0.0)
    one, = kernels.gmm_dw(x, (dya,), gs, tiles=(256, 256, 128),
                          interpret=True)
    assert _err(one, _loop_dw(x, dya, sizes)) <= BOUND[dtype]


@cases
@sizes_of
def test_grad_through_the_fused_pair_equals_the_unfused_composition(
        sizes, dtype):
    x, wgate, wup, wdown, gs = _operands(sizes, dtype)
    rng = np.random.default_rng(17)
    co = jnp.asarray(rng.normal(size=(ROWS, D)), jnp.float32)

    def loss(impl):
        def f(x, wgate, wup, wdown):
            act = entry.gated_product(x, wgate, wup, gs, impl)
            y = entry.product(act, wdown, gs, impl)
            return jnp.sum(y.astype(jnp.float32) * co), y
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(
            x, wgate, wup, wdown)
    (_, y), grads = loss("interpret")
    (_, want_y), want = loss("xla")
    held = sum(sizes)
    assert _err(y, want_y) <= 2 * BOUND[dtype]
    for a, b in zip(grads, want):
        assert a.dtype == b.dtype
        assert _err(a, b) <= 4 * BOUND[dtype]
    # rows past the last group: zero out, zero gradient in
    np.testing.assert_array_equal(np.asarray(y[held:], np.float32), 0.0)
    np.testing.assert_array_equal(np.asarray(grads[0][held:], np.float32),
                                  0.0)


def test_rows_that_are_no_whole_tile_are_padded_and_cut():
    sizes = (70, 0, 130, 31)
    rng = np.random.default_rng(19)
    x = jnp.asarray(rng.normal(size=(300, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, D, H)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = kernels.gmm(x, w, gs, interpret=True)
    assert got.shape == (300, H)
    assert _err(got, _loop(x, w, sizes)) <= BOUND[jnp.float32]
    dw, = kernels.gmm_dw(x, (got,), gs, interpret=True)
    assert _err(dw, _loop_dw(x, got, sizes)) <= BOUND[jnp.float32]


def test_widths_off_the_lane_tile_are_refused():
    x = jnp.zeros((128, 24), jnp.float32)
    w = jnp.zeros((2, 24, 128), jnp.float32)
    gs = jnp.asarray([5, 5], jnp.int32)
    with pytest.raises(ValueError, match="128-lane"):
        kernels.gmm(x, w, gs, interpret=True)
    x, w = jnp.zeros((128, 384), jnp.float32), jnp.zeros((2, 384, 128))
    with pytest.raises(ValueError, match="do not divide"):
        kernels.gmm(x, w, gs, tiles=(128, 256, 128), interpret=True)


def test_off_the_tpu_the_entry_lowers_to_ragged_dot():
    x, wgate, wup, wdown, gs = _operands(SIZES["empty_group"], jnp.bfloat16)
    assert jax.default_backend() != "tpu"
    assert entry._impl(x, wgate, wup) == "xla"

    def step(x, wgate, wup, wdown):
        def f(x, wgate, wup, wdown):
            act = entry.gated_grouped_matmul(x, wgate, wup, gs)
            return jnp.sum(entry.grouped_matmul(act, wdown, gs)
                           .astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2, 3))(x, wgate, wup, wdown)
    # the CPU's lowering takes ragged_dot apart; the traced program names it
    traced = str(jax.make_jaxpr(step)(x, wgate, wup, wdown))
    assert "ragged_dot" in traced and "pallas_call" not in traced
    text = jax.jit(step).lower(x, wgate, wup, wdown).as_text()
    assert "zoo_moe_gmm" not in text and "tpu_custom_call" not in text
    # what the layer counts where no kernel visits a tile
    assert int(entry.visited_tile_rows(wgate, gs, ROWS)) == 0


def test_visited_tile_rows_counts_the_visits():
    # group 0 fills its first tiles and cuts the one group 2 lies in (or
    # starts in); groups 1 and 3 are empty
    gs = jnp.asarray([600, 0, 100, 0], jnp.int32)
    tm = kernels._row_tile(1024)
    visits = -(-600 // tm) + (-(-700 // tm) - 600 // tm)
    assert int(kernels.visited_tile_rows(gs, 1024)) == visits * tm
    assert int(kernels._visits(gs, 1024, tm, False)[4][0]) == visits
    # the dW kernel's list gives each empty group a visit of its own
    assert int(kernels._visits(gs, 1024, tm, True)[4][0]) == visits + 2
    assert int(kernels.visited_tile_rows(jnp.zeros((4,), jnp.int32),
                                         1024)) == 0


@pytest.mark.parametrize("rows,k,n,itemsize", [
    (32768, 2304, 896, 2),          # the decoder cell's gate / up
    (32768, 896, 2304, 2),          # its down product
    (131072, 2304, 896, 2),         # the buffers not cut to the rows held
    (4096, 8192, 8192, 4),
    (1000, 128, 128, 2),
    (128, 16384, 256, 4),
])
def test_gmm_estimate_bounds_every_tiling_the_selector_returns(
        rows, k, n, itemsize):
    """The property of ``tests/test_pallas.py`` for the grouped products:
    the estimator zoolint loads standalone prices each kernel's tiles, the
    selector's choice fits the budget it was given under that formula (or
    is the floor tile, which cannot shrink), divides the widths and stays
    on the tile floors."""
    from analytics_zoo_tpu.analysis.device import footprint_module
    from analytics_zoo_tpu.ops.pallas.common import (LANES, round_up,
                                                     vmem_budget_bytes)

    lint = footprint_module()
    assert lint is not None
    for budget in (vmem_budget_bytes() // 2, 2 * vmem_budget_bytes(),
                   1 << 20):
        for kernel, pair, residuals in (
                ("fwd", False, False), ("fwd", True, False),
                ("fwd", True, True), ("dx", True, False),
                ("dw", False, False), ("dw", True, False)):
            tm, tk, tn = kernels.select_gmm_tiles(
                kernel, rows, k, n, itemsize, pair, residuals, budget)
            assert k % tk == 0 and n % tn == 0
            assert tk % LANES == 0 and tn % LANES == 0
            assert round_up(rows, kernels._ROW_FLOOR) % tm == 0
            assert tm >= kernels._ROW_FLOOR
            if kernel == "dx":
                assert tk == k
            est = lint.gmm_vmem_bytes(tm, tk, tn, itemsize, kernel=kernel,
                                      pair=pair, residuals=residuals)
            floor = (tm == kernels._ROW_FLOOR and tn == LANES
                     and (tk == LANES or kernel == "dx"))
            assert est <= budget or floor, (kernel, pair, (tm, tk, tn))


def test_routed_layer_runs_the_kernels_and_counts_their_tiles(monkeypatch):
    """``RoutedExperts`` through the kernels (interpreted), in its ``cond``
    between cut and whole buffers, its chunks and their remat: the result
    and gradients of the ``ragged_dot`` path, and ``gmm_tile_fill`` from
    the layer's counters."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import moe

    layer = moe.RoutedExperts(8, 128, top_k=2, held=range(2),
                              token_chunk=256)
    params = layer.build(jax.random.key(0), (2, 256, 128))
    x = jax.random.normal(jax.random.key(1), (2, 256, 128), jnp.float32)

    def run():
        def f(params, x):
            y, state = layer.apply(params, layer.initial_state(), x,
                                   training=True)
            return jnp.sum(y * y), (y, state)
        (_, (y, state)), grads = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(params, x)
        counts = {name: moe.wide_value(state[key])
                  for name, key in moe.WIDE_COUNTERS.items()}
        return y, grads, counts

    monkeypatch.setattr(entry, "_impl", lambda *a: "interpret")
    jax.clear_caches()          # _held_rows is jitted: no path from a cache
    y, grads, counts = run()
    monkeypatch.undo()
    jax.clear_caches()
    want_y, want_grads, want_counts = run()
    assert _err(y, want_y) <= BOUND[jnp.float32]
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert _err(a, b) <= 4 * BOUND[jnp.float32]
    assert counts["compact_runs"] == 2 and counts["held"] > 0
    # a tile two groups share is visited, and counted, once for each
    assert counts["held"] <= counts["gmm_tile_rows"]
    assert 0.0 < moe.bound_ratios(counts)["gmm_tile_fill"] <= 1.0
    # off the kernels no tile is visited and the ratio reads 0
    assert want_counts["gmm_tile_rows"] == 0
    assert moe.bound_ratios(want_counts)["gmm_tile_fill"] == 0.0
    assert {k: v for k, v in counts.items() if k != "gmm_tile_rows"} == {
        k: v for k, v in want_counts.items() if k != "gmm_tile_rows"}

