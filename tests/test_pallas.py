"""L0 Pallas kernels vs their XLA oracles (interpret mode on the CPU mesh;
the same code compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops.attention import dot_product_attention
from analytics_zoo_tpu.ops.pallas import flash_attention as flash_entry
from analytics_zoo_tpu.ops.pallas import int8_matmul

RTOL, ATOL = 2e-4, 2e-5


def flash_attention(q, k, v, **kw):
    """The entry on the oracle's (B, H, T, D) operands: it takes and gives
    (B, T, H, D) itself. The cases below that go through here keep the
    shapes they had (D = 8: the layout the wrapper splits); the in-place
    and pair layouts have their own cases at the entry's own layout."""
    return flash_entry(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                       **kw).transpose(0, 2, 1, 3)


def _qkv(b, h, tq, tk, d, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, h, tq, d)).astype(np.float32)
    k = rng.normal(size=(b, h, tk, d)).astype(np.float32)
    v = rng.normal(size=(b, h, tk, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla_multiblock(causal):
    # several q and k blocks, t NOT a multiple of the block size
    q, k, v = _qkv(2, 3, 50, 50, 8)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_flash_single_block_and_tiny():
    q, k, v = _qkv(1, 1, 3, 5, 4, seed=1)
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_cross_attention_lengths(causal):
    """tq != tkv, incl. the bottom-right-aligned causal convention."""
    q, k, v = _qkv(1, 2, 7, 33, 8, seed=2)
    out = flash_attention(q, k, v, causal=causal, block_q=4, block_k=8)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_flash_bf16():
    q, k, v = _qkv(1, 2, 32, 32, 8, seed=3)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = dot_product_attention(qb, kb, vb, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_xla(causal):
    q, k, v = _qkv(1, 2, 24, 24, 4, seed=4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=8, block_k=8) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_causal_tq_gt_tkv_zero_rows_have_zero_grad():
    """Forward zeroes query rows with no visible key (t_q > t_kv causal);
    the backward must treat those rows as constants — no uniform-weight
    gradient leak from the recompute reference."""
    q, k, v = _qkv(1, 1, 5, 3, 4, seed=8)
    out = flash_attention(q, k, v, causal=True, block_q=4, block_k=4)
    # rows 0..1 see no key (offset = 3 - 5 = -2): exactly zero
    np.testing.assert_array_equal(np.asarray(out[0, 0, :2]), 0.0)

    def f(v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=4, block_k=4)[0, 0, 0])

    g = jax.grad(f)(v)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_flash_rejects_nothing_when_t_one():
    q, k, v = _qkv(1, 1, 1, 1, 4, seed=5)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v),
                               rtol=RTOL, atol=ATOL)


def test_attention_layer_flash_optin_matches_xla_path():
    """zoo.pallas.attention=True routes MultiHeadSelfAttention through the
    flash kernel with identical results."""
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        MultiHeadSelfAttention

    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 12, 16)),
                    jnp.float32)
    layer = MultiHeadSelfAttention(16, 4, causal=True)
    params = layer.build(jax.random.key(0), (None, 12, 16))

    reset_zoo_context()
    init_zoo_context()
    y_xla = np.asarray(layer.call(params, x))
    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention": True})
    y_flash = np.asarray(layer.call(params, x))
    reset_zoo_context()
    np.testing.assert_allclose(y_flash, y_xla, rtol=RTOL, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_padding_mask_matches_xla(causal):
    """(B, Tk) keep-mask (the BERT attention_mask form) — forward parity with
    the XLA oracle's broadcast mask."""
    q, k, v = _qkv(2, 2, 20, 20, 8, seed=9)
    rng = np.random.default_rng(9)
    lens = rng.integers(5, 21, 2)
    mask = (np.arange(20)[None, :] < lens[:, None]).astype(np.float32)
    out = flash_attention(q, k, v, mask=jnp.asarray(mask), causal=causal,
                          block_q=8, block_k=8)
    ref = dot_product_attention(q, k, v, mask=jnp.asarray(mask)[:, None, None, :],
                                causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_masked_gradients_match_xla(causal):
    q, k, v = _qkv(2, 2, 16, 16, 4, seed=10)
    mask = jnp.asarray((np.arange(16)[None, :]
                        < np.array([[9], [16]])).astype(np.float32))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal,
                                       block_q=8, block_k=8) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, mask=mask[:, None, None, :], causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_fully_masked_row_zero_everywhere():
    """A batch row whose mask hides every key: zero output, zero grads —
    the lse=+inf sentinel path."""
    q, k, v = _qkv(2, 1, 6, 6, 4, seed=11)
    mask = jnp.asarray(np.stack([np.zeros(6), np.ones(6)]).astype(np.float32))
    out = flash_attention(q, k, v, mask=mask, block_q=4, block_k=4)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)

    def f(v):
        return jnp.sum(flash_attention(q, k, v, mask=mask,
                                       block_q=4, block_k=4)[0] ** 2)

    g = jax.grad(f)(v)
    np.testing.assert_array_equal(np.asarray(g[0]), 0.0)


def test_flash_bwd_no_quadratic_memory():
    """The backward must be the Pallas two-kernel scheme, not an XLA
    recompute that materializes (T, T): assert no O(T^2) intermediate in the
    jaxpr-compiled HLO at a length where (T,T) f32 would be 64 MB."""
    t = 4096
    q = jnp.zeros((1, 1, t, 8), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True))

    # abstract trace only — no execution needed to inspect shapes
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    biggest = 0
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                n = int(np.prod(var.aval.shape)) if var.aval.shape else 1
                biggest = max(biggest, n)
    # largest live tensor should be O(T*D), nowhere near T^2
    assert biggest < t * t // 8, f"O(T^2) intermediate found: {biggest}"
    # row statistics (lse, delta) are ONE float a row — no residual or
    # intermediate is T x 128 wide (the old lane-broadcast layout: as many
    # bytes a layer as q+k+v+o together at D=64)
    stats = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
             if getattr(v.aval, "dtype", None) == jnp.float32
             and len(v.aval.shape) >= 2 and v.aval.shape[-1] == 128
             and int(np.prod(v.aval.shape)) >= t * 128]
    assert not stats, f"lane-broadcast row statistics found: {stats}"
    lse = [v.aval.shape for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars
           if getattr(v.aval, "dtype", None) == jnp.float32
           and v.aval.shape == (1, 1, t)]
    assert lse, "no (B*H, 1, T) row statistic in the backward's jaxpr"


def test_flash_gradients_bf16():
    q, k, v = _qkv(1, 2, 32, 32, 8, seed=12)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=16, block_k=16)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss, argnums=(0, 1, 2))(qb, kb, vb)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=1e-1, atol=1e-1)


def test_attention_layer_flash_handles_bert_mask():
    """With flash forced on, a (B, 1, 1, T) padding mask routes through the
    kernel (not the XLA fallback) and matches the XLA path."""
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    from analytics_zoo_tpu.pipeline.api.keras.layers import \
        MultiHeadSelfAttention

    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
    mask = jnp.asarray((np.arange(12)[None, :]
                        < np.array([[7], [12]])).astype(np.float32)
                       )[:, None, None, :]
    layer = MultiHeadSelfAttention(16, 4)
    params = layer.build(jax.random.key(0), (None, 12, 16))

    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention": False})
    y_xla = np.asarray(layer.call(params, [x, mask]))
    reset_zoo_context()
    init_zoo_context(conf={"zoo.pallas.attention": True})
    assert layer._use_flash(mask, 0.0, 12)
    y_flash = np.asarray(layer.call(params, [x, mask]))
    reset_zoo_context()
    np.testing.assert_allclose(y_flash, y_xla, rtol=RTOL, atol=1e-4)


# ---------------------------------------------------------------------------
# heads in place: the entry's own (B, T, H, D) layout, the three ways the
# kernels find a head in it
# ---------------------------------------------------------------------------

#: name -> (layout, B, T, Hq, Hkv, D, causal, window, masked). Blocks are
#: pinned at (64, 128): several q blocks and k tiles at these lengths.
_LAYOUT_CASES = {
    # whole lane tiles: a head is a lane block of the (T, H * D) plane
    "inplace_d128": ("inplace", 2, 256, 2, 2, 128, True, None, False),
    "inplace_d256": ("inplace", 1, 128, 2, 2, 256, True, None, False),
    "inplace_grouped": ("inplace", 1, 256, 4, 2, 128, True, None, False),
    "inplace_grouped_window": ("inplace", 1, 256, 4, 2, 128, True, 100,
                               False),
    "inplace_key_mask": ("inplace", 2, 200, 2, 2, 128, False, None, True),
    # half a lane tile: two heads a block
    "pair": ("pair", 2, 256, 2, 2, 64, True, None, False),
    "pair_key_mask": ("pair", 2, 256, 4, 4, 64, False, None, True),
    "pair_unaligned": ("pair", 1, 200, 4, 4, 64, True, None, False),
    # grouped 4:1: both heads of a pair attend ONE key head, which stands
    # in the first half of its block for pairs 0-1, in the second for 2-3
    "pair_grouped_4to1": ("pair", 1, 256, 8, 2, 64, True, None, False),
    "pair_grouped_2to1_window": ("pair", 1, 200, 8, 4, 64, True, 70, False),
    # neither: the wrapper splits the heads out
    "split_d8": ("split", 2, 40, 2, 2, 8, True, None, False),
    "split_odd_heads_d64": ("split", 1, 256, 3, 3, 64, True, None, False),
    "split_grouped_3to1_d64": ("split", 1, 128, 6, 2, 64, True, None, False),
}


@pytest.mark.parametrize("name", sorted(_LAYOUT_CASES))
def test_flash_entry_layouts_match_xla(name):
    """The entry on (B, T, H, D) against ``dot_product_attention``, values
    and the three gradients through the interpreter, and the call takes
    the layout the case names."""
    import importlib
    fa_mod = importlib.import_module(
        "analytics_zoo_tpu.ops.pallas.flash_attention")
    layout, b, t, h, h_kv, d, causal, window, masked = _LAYOUT_CASES[name]
    assert fa_mod._head_layout(h, h_kv, d).layout == layout
    rng = np.random.default_rng(7)
    q, g = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(b, t, h_kv, d)), jnp.float32)
            for _ in range(2))
    mask = None
    if masked:
        keep = np.ones((b, t), np.float32)
        keep[1, t * 5 // 8:] = 0.0
        keep[0, 3] = 0.0
        mask = jnp.asarray(keep)

    def flash(q, k, v):
        return flash_entry(q, k, v, mask=mask, causal=causal, window=window,
                           block_q=64, block_k=128)

    def oracle(q, k, v):
        m4 = None if mask is None else mask[:, None, None, :]
        return dot_product_attention(
            *(a.transpose(0, 2, 1, 3) for a in (q, k, v)), mask=m4,
            causal=causal, window=window).transpose(0, 2, 1, 3)

    out = flash(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(q, k, v)),
                               rtol=RTOL, atol=ATOL)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * g), argnums=(0, 1, 2))(
        q, k, v)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=2e-3, atol=2e-4)


def test_pair_cells_run_in_bf16_as_on_the_chip():
    """The pair layout at the operands' real dtype: the lane selects and
    the half-tile rotation of a grouped pair go through float32 inside the
    cell and must come back exact."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 256, 8, 64)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.bfloat16)
            for _ in range(2))
    out = flash_entry(q, k, v, causal=True, block_q=64, block_k=128)
    ref = dot_product_attention(*(a.transpose(0, 2, 1, 3)
                                  for a in (q, k, v)), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.transpose(0, 2, 1, 3),
                                          np.float32), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# the tile schedule: resident major windows, diagonal-bounded loops,
# mask-free interior tiles
# ---------------------------------------------------------------------------

#: name -> (t_q, t_kv, causal, masked keys from, vmem_budget_mb, several).
#: Blocks are pinned at (128, 128), so the forward's tile is (128, 256).
#: ``several``: the budget cuts every kernel's streamed side into more than
#: one major window (else: exactly one).
_SCHEDULE_CASES = {
    # the whole sequence resident; diagonal AND interior tiles in one call
    # (4 q blocks against 2 wide k tiles)
    "whole_causal_aligned": (512, 512, True, None, None, False),
    # T not a multiple of the blocks: the last tile is a masked one
    "whole_causal_unaligned": (450, 450, True, None, None, False),
    "whole_noncausal_unaligned": (300, 450, False, None, None, False),
    # a small budget cuts the streamed side into several windows; the
    # causal ones right of the diagonal are skipped steps
    "windows_causal": (512, 512, True, None, 0.5, True),
    "windows_noncausal_unaligned": (450, 450, False, None, 0.5, True),
    # t_q != t_kv both ways (bottom-right alignment; t_q > t_kv has rows,
    # and whole q blocks, that see no key)
    "causal_tq_lt_tkv": (200, 512, True, None, None, False),
    "causal_tq_gt_tkv": (512, 200, True, None, None, False),
    "windows_causal_tq_gt_tkv": (640, 384, True, None, 0.5, True),
    # key padding: keys from 200 on hidden, so the tiles from 256 on are
    # fully padded ones
    "mask_padded_tile": (384, 512, False, 200, None, False),
    "mask_causal_windows": (512, 512, True, 300, 0.5, True),
    # a causal window and grouped heads (two more fields: window, query
    # heads to a key/value head); T = 450 is not a multiple of the tile.
    # window 8 lies inside one tile, 128 spans a tile's edge
    "gqa4_full_unaligned": (450, 450, True, None, None, False, None, 4),
    "window8_unaligned": (450, 450, True, None, None, False, 8, 1),
    "window8_gqa4_unaligned": (450, 450, True, None, None, False, 8, 4),
    "window128_unaligned": (450, 450, True, None, None, False, 128, 1),
    "window128_gqa4_unaligned": (450, 450, True, None, None, False, 128, 4),
    # several major windows: the steps behind the window are skipped ones
    "windows_window128_gqa4": (640, 640, True, None, 0.5, True, 128, 4),
    # a window with a key-padding mask: every tile of it a masked one
    # (keys hidden from 450 on: the last row's window still holds 65 keys
    # that are not)
    "mask_window128": (512, 512, True, 450, None, False, 128, 1),
}


@pytest.mark.parametrize("name", sorted(_SCHEDULE_CASES))
def test_flash_schedule_matches_xla(name):
    """Forward and dq/dk/dv of each schedule case against ``ops.attention``
    through the interpreter, and the case really is the schedule it
    names."""
    import importlib

    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    fa_mod = importlib.import_module(
        "analytics_zoo_tpu.ops.pallas.flash_attention")
    (t_q, t_kv, causal, hide_from, budget_mb, several,
     *extra) = _SCHEDULE_CASES[name]
    window, group = extra or (None, 1)
    q, k, v = _qkv(2, 2, t_q, t_kv, 8, seed=40)
    if group > 1:       # `group` query heads to each of the 2 k/v heads
        q = jnp.asarray(np.random.default_rng(42).normal(
            size=(2, 2 * group, t_q, 8)), jnp.float32)
    mask = None
    if hide_from is not None:
        keep = np.ones((2, t_kv), np.float32)
        keep[1, hide_from:] = 0.0
        mask = jnp.asarray(keep)
    g = jnp.asarray(np.random.default_rng(41).normal(size=q.shape),
                    jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal,
                                       block_q=128, block_k=128,
                                       window=window) * g)

    def loss_ref(q, k, v):
        m4 = None if mask is None else mask[:, None, None, :]
        return jnp.sum(dot_product_attention(q, k, v, mask=m4, causal=causal,
                                             window=window) * g)

    try:
        reset_zoo_context()
        if budget_mb:
            init_zoo_context(conf={"zoo.pallas.vmem_budget_mb": budget_mb})
        sched = fa_mod._resolve_schedule(t_q, t_kv, 8, q.dtype,
                                         mask is not None, 128, 128)
        assert sched.fwd.block_k == 256 and sched.dq.block_k == 128
        windows = [-(-t // s.major) for t, s in zip((t_kv, t_kv, t_q),
                                                    sched)]
        assert all(n > 1 if several else n == 1 for n in windows), sched
        out = flash_attention(q, k, v, mask=mask, causal=causal,
                              block_q=128, block_k=128, window=window)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    finally:
        reset_zoo_context()
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    m4 = None if mask is None else mask[:, None, None, :]
    ref = np.asarray(dot_product_attention(q, k, v, mask=m4, causal=causal,
                                           window=window))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    out = np.asarray(out)
    # rows that see no key: zeros here, uniform weights in the oracle
    # (causal t_q > t_kv: the first t_q - t_kv rows). They take no part
    # in any other row, so the comparison leaves them out
    dead = max(t_q - t_kv, 0) if causal else 0
    np.testing.assert_array_equal(out[:, :, :dead], 0.0)
    np.testing.assert_allclose(out[:, :, dead:], ref[:, :, dead:],
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_array_equal(np.asarray(gf[0])[:, :, :dead], 0.0)
    if dead == 0:
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
    else:
        # the oracle's uniform rows leak gradient into k and v; compare
        # against the oracle on the live rows alone
        def loss_live(q, k, v):
            o = dot_product_attention(q[:, :, dead:], k, v, causal=causal)
            return jnp.sum(o * g[:, :, dead:])
        gl = jax.grad(loss_live, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
    if mask is not None:
        # a hidden key gets no gradient at all
        np.testing.assert_array_equal(np.asarray(gf[1])[1, :, hide_from:],
                                      0.0)
        np.testing.assert_array_equal(np.asarray(gf[2])[1, :, hide_from:],
                                      0.0)


@pytest.mark.parametrize("t_q,t_kv,causal,has_mask,tiling,window", [
    (4096, 4096, True, False, (512, 1024, 4096), None),
    (4096, 4096, True, False, (256, 512, 4096), None),
    (4096, 4096, True, False, (256, 512, 1024), None),   # several windows
    (1000, 1000, True, False, (256, 512, 1024), None),
    (512, 200, True, False, (128, 128, 256), None),  # q blocks see nothing
    (2048, 2048, False, True, (512, 1024, 2048), None),  # mask: all masked
    (2048, 2048, False, False, (512, 1024, 2048), None),     # none masked
    # a window: the loops start at the first tile it reaches
    (8192, 8192, True, False, (512, 1024, 8192), 1024),
    (8192, 8192, True, False, (512, 512, 2048), 1024),   # several windows
    (1000, 1000, True, False, (256, 512, 1024), 100),
    (640, 640, True, False, (128, 128, 640), 8),     # inside one tile
    (2048, 2048, True, True, (512, 1024, 2048), 512),    # mask: all masked
])
def test_flash_tile_census_counts_the_loops(t_q, t_kv, causal, has_mask,
                                            tiling, window):
    """The census the gauge publishes against a brute-force count over the
    (q, k) plane: a tile is needed iff it holds a visible key, interior iff
    every element of it is visible and the call has no key mask."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (_Tiling,
                                                              _tile_census)
    bq, bk, major = tiling
    census = _tile_census(t_q, t_kv, _Tiling(*tiling), causal, has_mask,
                          window)
    rows = np.arange(-(-t_q // bq) * bq)[:, None]
    keys = np.arange(-(-t_kv // major) * major)[None, :]
    vis = (keys < t_kv) & np.ones_like(rows, bool)
    if causal:
        vis = vis & (keys <= rows + (t_kv - t_q))
    causal_tiles = vis.reshape(rows.shape[0] // bq, bq, -1, bk).any(
        axis=(1, 3))
    if window is not None:
        vis = vis & (keys > rows + (t_kv - t_q) - window)
    tiles = vis.reshape(rows.shape[0] // bq, bq, keys.shape[1] // bk, bk)
    # a q block's loops run over one run of tiles, from the first to the
    # last that holds a visible key: visibility is an interval of the keys
    # in every row, so every tile between those two holds one too
    any_vis = tiles.any(axis=(1, 3))
    all_vis = tiles.all(axis=(1, 3))
    assert all(np.all(np.diff(r.nonzero()[0]) == 1) for r in any_vis)
    needed = int(any_vis.sum())
    interior = 0 if has_mask else int((all_vis & any_vis).sum())
    assert census["interior"] + census["masked"] == needed
    assert census["interior"] == interior
    per_window = any_vis.reshape(any_vis.shape[0], -1, major // bk)
    assert census["skipped_steps"] == int(
        (~per_window.any(axis=2)).sum())
    if window is None:
        assert "skipped_tiles" not in census
    else:
        assert census["skipped_tiles"] == int(causal_tiles.sum()) - needed


def test_window_census_worked_by_hand():
    """T = 8192, window 1024, the forward's tile (512, 1024), the whole
    sequence resident. Rows 512 b .. 512 b + 511 see keys from
    512 b - 1023 to 512 b + 511. Even b = 2 m: the keys reach from tile
    m - 1 (cut by the window's trailing edge: masked) into tile m (cut by
    the diagonal: masked); block 0 has tile 0 alone: 1 + 7 x 2 = 15
    masked. Odd b = 2 m + 1: rows 1024 m + 512 .. 1024 m + 1023 see keys
    1024 m - 511 .. 1024 m + 1023: tile m - 1 (trailing edge) and tile m
    (the diagonal crosses it; its last row sees all of it); block 1 has
    tile 0 alone: 1 + 7 x 2 = 15 masked. No tile is wholly visible to
    all 512 rows (that takes window >= 512 + 1024 - 1), so 0 interior.
    The causal half has sum_b (b // 2 + 1) = 2 x 36 = 72 tiles; 30 are
    computed, 42 skipped."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (_Tiling,
                                                              _tile_census)
    census = _tile_census(8192, 8192, _Tiling(512, 1024, 8192), True, False,
                          1024)
    assert census == {"interior": 0, "masked": 30, "skipped_steps": 0,
                      "skipped_tiles": 42}
    # the backward's (512, 512) tile has room for mask-free tiles: rows
    # 512 b .. see keys 512 b - 1023 .. 512 b + 511, i.e. tiles b - 2
    # (trailing edge), b - 1 (whole, for every row) and b (diagonal)
    census = _tile_census(8192, 8192, _Tiling(512, 512, 8192), True, False,
                          1024)
    assert census == {"interior": 15, "masked": 16 + 14,
                      "skipped_steps": 0, "skipped_tiles": 136 - 45}
    # and the full layer's census is what it was without the argument
    assert _tile_census(8192, 8192, _Tiling(512, 1024, 8192), True,
                        False) == {"interior": 56, "masked": 16,
                                   "skipped_steps": 0}


def test_auto_schedule_metric_names_windows_and_census():
    """The ``choice`` label names the schedule and the sibling gauge holds
    the static census: the benchmark's GPT-1 signature keeps the whole
    sequence resident, skips no grid step, and interior + masked are the
    tiles on or under the diagonal."""
    import importlib

    from analytics_zoo_tpu.observability import default_registry
    fa_mod = importlib.import_module(
        "analytics_zoo_tpu.ops.pallas.flash_attention")
    sched = fa_mod._auto_blocks((8, 4096, 12, 64), 4096, jnp.bfloat16, True,
                                False, True)
    assert sched.fwd == (512, 1024, 4096)
    assert sched.dq == sched.dkv == (512, 512, 4096)
    snap = default_registry().snapshot()
    sig = 'sig="tq4096tk4096d64bfloat16c"'
    choice = [k for k in snap if k.startswith("zoo_pallas_block_choice")
              and sig in k]
    # ... and ends in the layout the call takes: 12 heads of 64, in pairs
    assert len(choice) == 1 and ("fwd=512x1024/kmajor4096,"
                                 "dq=512x512/kmajor4096,"
                                 "dkv=512x512/qmajor4096,"
                                 "heads=pair") in choice[0]
    tiles = {kind: snap[k]["value"] for kind in
             ("interior", "masked", "skipped_steps")
             for k in snap if k.startswith("zoo_pallas_flash_tiles")
             and sig in k and f'kind="{kind}"' in k}
    assert tiles["skipped_steps"] == 0
    # 8 q blocks of 512 rows against 1024-wide tiles: block i needs
    # ceil(512 (i + 1) / 1024) of them
    assert tiles["interior"] + tiles["masked"] == sum(
        -(-512 * (i + 1) // 1024) for i in range(8)) == 20
    assert tiles["masked"] == 8          # one diagonal tile a q block
    # the long-context signature cannot keep 32k keys resident: several
    # windows, and the causal steps right of the diagonal are counted
    long = fa_mod._auto_blocks((1, 32768, 12, 64), 32768, jnp.bfloat16,
                               True, False, True)
    assert long.fwd.major < 32768 and long.dkv.major < 32768
    snap = default_registry().snapshot()
    skipped = [snap[k]["value"] for k in snap
               if k.startswith("zoo_pallas_flash_tiles")
               and 'sig="tq32768tk32768d64bfloat16c"' in k
               and 'kind="skipped_steps"' in k]
    assert skipped and skipped[0] > 0


# ---------------------------------------------------------------------------
# int8 weight-only matmul
# ---------------------------------------------------------------------------

def test_int8_matmul_matches_dequant():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(37, 19)).astype(np.float32)
    w = rng.integers(-127, 128, (19, 29)).astype(np.int8)
    s = rng.uniform(0.01, 0.1, 29).astype(np.float32)
    out = int8_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                      block_m=16, block_n=8)
    ref = x @ (w.astype(np.float32) * s[None, :])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-4)


def test_int8_matmul_shape_check():
    with pytest.raises(ValueError):
        int8_matmul(jnp.zeros((4, 3)), jnp.zeros((5, 2), jnp.int8),
                    jnp.zeros(2))


# ---------------------------------------------------------------------------
# flash-attention block autotuning
# ---------------------------------------------------------------------------

def test_select_blocks_defaults_to_swept_sweet_spot():
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _PREFERRED_BLOCKS, select_attention_blocks)
    # the bench long-context shape and the benchmark's GPT-1 cell: D=64
    # bf16 fits VMEM at the swept default
    assert _PREFERRED_BLOCKS == (512, 512)
    for t in (32768, 4096):
        assert select_attention_blocks(t, t, 64, jnp.bfloat16,
                                       causal=True) == _PREFERRED_BLOCKS


def test_select_blocks_shrinks_for_vmem_budget():
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _PREFERRED_BLOCKS, _kernel_vmem_bytes, select_attention_blocks)
    # a tight explicit budget must shrink the blocks until the estimate
    # (the largest of the three kernels at that pair) fits
    bq, bk = select_attention_blocks(8192, 8192, 256, jnp.float32,
                                     budget_bytes=2 * 1024 * 1024)
    assert (bq, bk) != _PREFERRED_BLOCKS
    assert _kernel_vmem_bytes(bq, bk, 256, 4) <= 2 * 1024 * 1024
    # monotone: a huge budget returns the preferred default
    assert select_attention_blocks(8192, 8192, 256, jnp.float32,
                                   budget_bytes=1 << 30) == _PREFERRED_BLOCKS


def test_select_blocks_clamps_to_short_sequences():
    from analytics_zoo_tpu.ops.pallas.flash_attention import \
        select_attention_blocks
    bq, bk = select_attention_blocks(50, 50, 8, jnp.float32)
    assert bq <= 56 and bk <= 128      # rounded-up T bounds


@pytest.mark.parametrize("t_q,t_kv,d,budget", [
    (200, 200, 256, 1 << 20),      # unaligned T + tight budget
    (50, 1000, 512, 1 << 19),      # shrink all the way to the floors
    (8192, 8192, 128, 3 << 20),
])
def test_select_blocks_stay_tile_aligned_under_any_budget(t_q, t_kv, d,
                                                          budget):
    """The shrink loop must re-round every halving — an odd clamped block
    (56 -> 28) would hand Mosaic an untileable pair on the DEFAULT path."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _LANES, _SUBLANES, select_attention_blocks)
    bq, bk = select_attention_blocks(t_q, t_kv, d, jnp.float32,
                                     budget_bytes=budget)
    assert bq % _SUBLANES == 0 and bq >= _SUBLANES, (bq, bk)
    assert bk % _LANES == 0 and bk >= _LANES, (bq, bk)


def test_auto_blocks_cached_and_metric_emitted():
    import importlib

    from analytics_zoo_tpu.observability import default_registry

    # the package __init__ rebinds `flash_attention` to the function —
    # go through importlib for the module itself
    fa_mod = importlib.import_module(
        "analytics_zoo_tpu.ops.pallas.flash_attention")
    q, k, v = _qkv(1, 2, 40, 40, 8, seed=20)
    out = flash_attention(q, k, v, causal=True)        # auto blocks
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=2e-5)
    cache = fa_mod._BLOCK_CACHE
    # heuristic entries key on (budget, T, D, dtype, ...) only —
    # batch/heads must not fragment the cache (a ragged final batch
    # would re-resolve), but a changed VMEM budget must
    budget = int(fa_mod._VMEM_BYTES_DEFAULT * fa_mod._VMEM_USABLE_FRACTION)
    sig = (budget, 40, 40, 8, "float32", True, False, "split")
    assert sig in cache, f"signature not cached: {sorted(cache)}"
    n_before = len(cache)
    flash_attention(q, k, v, causal=True)              # second call: cached
    q2, k2, v2 = _qkv(2, 2, 40, 40, 8, seed=22)        # new batch, same T/D
    flash_attention(q2, k2, v2, causal=True)
    assert len(cache) == n_before                      # no re-resolution
    snap = default_registry().snapshot()
    assert any(key.startswith("zoo_pallas_block_choice") for key in snap), \
        "block choice not surfaced as an info metric"


def test_block_cache_respects_budget_reconfiguration():
    """Re-initializing the context with a different vmem budget must not
    hit stale cache entries sized for the old budget."""
    import importlib

    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    fa_mod = importlib.import_module(
        "analytics_zoo_tpu.ops.pallas.flash_attention")
    q, k, v = _qkv(1, 1, 2048, 2048, 256, seed=23)
    try:
        reset_zoo_context()
        init_zoo_context(conf={"zoo.pallas.vmem_budget_mb": 4})
        shape = (1, 2048, 1, 256)
        small = fa_mod._auto_blocks(shape, 2048, q.dtype, False, False,
                                    True)
        reset_zoo_context()
        init_zoo_context()                   # default 16 MiB budget
        big = fa_mod._auto_blocks(shape, 2048, q.dtype, False, False,
                                  True)
        assert small != big, "budget change did not re-resolve the blocks"
    finally:
        reset_zoo_context()


def test_sweep_candidates_are_tile_aligned_on_unaligned_sequences():
    """Clamping a candidate against an unaligned T must round to the
    sublane/lane tile floors — a raw (128, 1000) pair can only fail to
    compile and silently shrink the candidate pool."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _LANES, _SUBLANES, _sweep_candidates)
    for bq, bk in _sweep_candidates(1000, 1000, 64, 2, False, (512, 512)):
        assert bq % _SUBLANES == 0 and bk % _LANES == 0, (bq, bk)


def test_block_sweep_picks_fastest_candidate_via_injected_timer():
    """The sweep machinery with a stubbed timer: the candidate the timer
    favors wins; real on-device timing is TPU-only."""
    from analytics_zoo_tpu.ops.pallas.flash_attention import _sweep_blocks

    timed = []

    def timer(bq, bk):
        timed.append((bq, bk))
        return 0.001 if (bq, bk) == (256, 512) else 1.0

    best = _sweep_blocks(1, 2, 2048, 2048, 64, jnp.bfloat16, True, False,
                         (512, 512), timer=timer)
    assert best == (256, 512)
    assert (512, 512) in timed and len(timed) >= 3


def test_sweep_candidate_failure_loses_not_raises():
    from analytics_zoo_tpu.ops.pallas.flash_attention import _sweep_blocks

    def timer(bq, bk):
        if (bq, bk) == (512, 512):
            raise RuntimeError("compile failed")
        return 1.0 if (bq, bk) != (256, 256) else 0.5

    best = _sweep_blocks(1, 1, 1024, 1024, 64, jnp.float32, False, False,
                         (512, 512), timer=timer)
    assert best == (256, 256)


def test_explicit_blocks_still_pin():
    """Passing explicit blocks bypasses auto selection entirely (the
    reproduction/debug path every earlier test in this file relies on)."""
    q, k, v = _qkv(1, 1, 33, 33, 4, seed=21)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# the shared VMEM footprint estimator: lint-time == runtime, by property
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t_q,t_kv,d,itemsize,has_mask", [
    (32768, 32768, 64, 2, False),
    (32768, 32768, 64, 2, True),
    (8192, 8192, 256, 4, False),
    (2048, 4096, 128, 4, True),
    (1000, 1000, 64, 2, False),
    (512, 512, 512, 4, False),
])
def test_lint_estimate_equals_autotuner_decisions(t_q, t_kv, d, itemsize,
                                                  has_mask):
    """The property the ZL024 satellite demands: the estimator zoolint
    loads standalone (no jax) prices every candidate IDENTICALLY to the
    runtime autotuner — for the FULL raw candidate set, a candidate
    survives `_sweep_candidates` exactly when the lint-side estimate
    fits the usable budget, and the heuristic's final choice fits it
    too. And it describes the windows the kernels really get: every
    kernel's tiling of the resolved schedule, major window and all, is
    priced inside the whole per-core budget by the same function."""
    from analytics_zoo_tpu.analysis.device import footprint_module
    from analytics_zoo_tpu.ops.pallas.common import (
        LANES, SUBLANES, round_up, vmem_budget_bytes, vmem_usable_bytes)
    from analytics_zoo_tpu.ops.pallas.flash_attention import (
        _SWEEP_PAIRS, _resolve_schedule, _sweep_candidates,
        select_attention_blocks)

    lint = footprint_module()
    assert lint is not None
    # a head wider than a lane tile is fitted into two budgets (PR 33)
    scale = lint.attention_budget_scale(d)
    assert scale == (2 if d > 128 else 1)
    budget = vmem_usable_bytes() * scale
    dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
    heuristic = select_attention_blocks(t_q, t_kv, d, dtype,
                                        has_mask=has_mask)
    kept = _sweep_candidates(t_q, t_kv, d, itemsize, has_mask, heuristic)
    expected = []
    for bq, bk in (heuristic,) + _SWEEP_PAIRS:
        cand = (max(SUBLANES, min(bq, round_up(max(t_q, 1), SUBLANES))),
                max(LANES, min(bk, round_up(max(t_kv, 1), LANES))))
        if cand in expected:
            continue
        if lint.attention_vmem_bytes(*cand, d=d, itemsize=itemsize,
                                     has_mask=has_mask) <= budget:
            expected.append(cand)
    # the runtime keeps exactly the candidates the lint-side estimator
    # says fit (falling back to the heuristic when nothing does)
    assert kept == (expected or [heuristic])
    # the heuristic choice the runtime actually runs fits the budget
    # under the SAME formula (or is the floor pair, which cannot shrink)
    bq, bk = heuristic
    assert (lint.attention_vmem_bytes(bq, bk, d=d, itemsize=itemsize,
                                      has_mask=has_mask) <= budget
            or (bq, bk) == (SUBLANES, LANES))
    # the windows: each kernel's own tile with its major window, as the
    # pallas_calls are built, inside the whole budget (a floor tile that
    # alone is over it keeps a one-tile window)
    sched = _resolve_schedule(t_q, t_kv, d, dtype, has_mask, bq, bk)
    assert sched.fwd.block_k == min(
        lint.ATTENTION_FWD_K_TILES * sched.dq.block_k,
        round_up(t_kv, LANES))
    for kernel, t in zip(sched._fields, sched):
        blk = t.block_q if kernel == "dkv" else t.block_k
        assert t.major % blk == 0 and t.major >= blk
        est = lint.attention_vmem_bytes(
            t.block_q, t.block_k, d=d, itemsize=itemsize,
            has_mask=has_mask, major=t.major, kernel=kernel)
        assert est <= vmem_budget_bytes() * scale or t.major == blk, (
            kernel, t)


def test_fused_ce_budget_clamp_consumes_shared_estimator():
    """cross_entropy.fused_ce_forward shrinks its blocks with the SAME
    ce_vmem_bytes formula: at a hidden width where the default
    (256, 512) blocks provably outgrow the usable budget, the clamp
    lands on a configuration that fits — and the kernel still matches
    the oracle bit-for-bit after the shrink."""
    from analytics_zoo_tpu.ops.pallas.common import (ce_vmem_bytes,
                                                     vmem_usable_bytes)
    from analytics_zoo_tpu.ops.pallas.cross_entropy import _budget_blocks

    budget = vmem_usable_bytes()
    # hidden=4096 bf16: the default blocks do NOT fit half of 16 MiB
    assert ce_vmem_bytes(256, 512, 4096, 2) > budget
    bn, bv = _budget_blocks(256, 512, 4096, 2, True)
    assert ce_vmem_bytes(bn, bv, 4096, 2) <= budget
    assert bn % 8 == 0 and bv % 128 == 0 and (bn, bv) != (256, 512)
    # deterministic: the same signature always clamps to the same blocks
    # (jit caches stay stable)
    assert (bn, bv) == _budget_blocks(256, 512, 4096, 2, True)
    # a hidden width whose floor cost already exceeds the budget stops
    # at the tile floors instead of spinning
    assert _budget_blocks(256, 512, 8192, 4, True) == (8, 128)


# ---------------------------------------------------------------------------
# fused-CE backward kernel pair (ops/pallas/cross_entropy.fused_ce_backward)
# ---------------------------------------------------------------------------

def _ce_bwd_case(n=37, h=24, v=130, seed=0, bias=True):
    rng = np.random.default_rng(seed)
    hid = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(h, v)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(v,)) * 0.1, jnp.float32) if bias \
        else None
    y = np.array(rng.integers(0, v, n), np.int32)
    y[::5] = -1
    return hid, w, b, jnp.asarray(y)


@pytest.mark.parametrize("bias", [True, False])
def test_ce_backward_kernel_matches_xla_scan(bias):
    """The Pallas CE backward pair under interpret mode vs the XLA scan
    formulation — dh, dW and db at an odd N (row padding) and odd V
    (vocab-tile padding), masked labels included. Tiles are re-formed
    with the same compute-dtype rounding, so the only drift is the
    block-order reassociation of the f32 accumulators."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (_bwd_scan,
                                                           _fwd_scan,
                                                           _grad_scale)
    from analytics_zoo_tpu.ops.pallas.cross_entropy import fused_ce_backward

    hid, w, b, y = _ce_bwd_case(bias=bias)
    lse, _ = _fwd_scan(hid, w, b, y, chunk=8)
    g = jnp.asarray(np.random.default_rng(1).normal(size=(37,)),
                    jnp.float32)
    scale = _grad_scale(y, g, w.shape[1])
    dh_x, dw_x, db_x = _bwd_scan(hid, w, b, y, lse, scale, chunk=8)
    dh_p, dw_p, db_p = fused_ce_backward(hid, w, b, y, lse, scale,
                                         block_n=8, block_v=128,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(dh_p), np.asarray(dh_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw_p), np.asarray(dw_x),
                               rtol=1e-5, atol=1e-6)
    if bias:
        np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_x),
                                   rtol=1e-5, atol=1e-6)
    else:
        assert db_p is None


def test_ce_backward_kernel_bf16_f32_accumulation():
    """bf16 operands: the kernels accumulate in f32
    (preferred_element_type) and return f32 dW — parity with the XLA
    scan stays tight even though the tile logits are bf16-rounded."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (_bwd_scan,
                                                           _fwd_scan,
                                                           _grad_scale)
    from analytics_zoo_tpu.ops.pallas.cross_entropy import fused_ce_backward

    hid, w, b, y = _ce_bwd_case(n=48, h=16, v=256, seed=3)
    hb = hid.astype(jnp.bfloat16)
    lse, _ = _fwd_scan(hb, w, b, y, chunk=16)
    scale = _grad_scale(y, jnp.ones((48,)), w.shape[1])
    dh_x, dw_x, db_x = _bwd_scan(hb, w, b, y, lse, scale, chunk=16)
    dh_p, dw_p, db_p = fused_ce_backward(hb, w.astype(jnp.bfloat16), b, y,
                                         lse, scale, block_n=16,
                                         block_v=128, interpret=True)
    assert dw_p.dtype == jnp.float32
    assert dh_p.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(dw_p), np.asarray(dw_x),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_x),
                               rtol=2e-2, atol=2e-2)


def test_ce_backward_over_range_label_poisons():
    """An over-range label's NaN grad-scale spreads through both product
    matmuls — dW and dh are NaN exactly like the XLA formulation."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (_fwd_scan,
                                                           _grad_scale)
    from analytics_zoo_tpu.ops.pallas.cross_entropy import fused_ce_backward

    hid, w, b, _ = _ce_bwd_case(n=16, h=8, v=64, seed=5)
    y = np.arange(16, dtype=np.int32)
    y[3] = 200
    y = jnp.asarray(y)
    lse, _ = _fwd_scan(hid, w, b, jnp.clip(y, 0, 63), chunk=8)
    scale = _grad_scale(y, jnp.ones((16,)), 64)
    dh, dw, db = fused_ce_backward(hid, w, b, jnp.where(y < 64, y, 64),
                                   lse, scale, block_n=8, interpret=True)
    assert np.isnan(np.asarray(dw)).all()
    assert np.isnan(np.asarray(dh)[3]).all()


def test_end_to_end_pallas_ce_grads_match_oracle():
    """jax.grad through fused CE with the FULL pallas routing (forward
    kernel + backward kernel pair, interpret mode) vs the full-logits
    oracle — the user-facing equivalence the tri-state flag promises."""
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        fused_sparse_cross_entropy)
    from analytics_zoo_tpu.pipeline.api.keras import objectives

    hid, w, b, y = _ce_bwd_case()
    yv = jnp.where(y < 0, 0, y)

    def oracle(hid, w, b):
        pe = objectives.sparse_categorical_crossentropy_from_logits_pe(
            yv, hid @ w + b)
        valid = (y >= 0).astype(jnp.float32)
        return jnp.sum(pe * valid) / jnp.sum(valid)

    gf = jax.grad(lambda hid, w, b: fused_sparse_cross_entropy(
        y, hid, w, b, chunk=8, use_pallas=True, interpret=True),
        argnums=(0, 1, 2))(hid, w, b)
    go = jax.grad(oracle, argnums=(0, 1, 2))(hid, w, b)
    for a, bb in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_ce_bwd_budget_clamp_and_estimator_agreement():
    """The backward block selector prices with the SAME
    ``ce_bwd_vmem_bytes`` formula zoolint loads standalone: every sweep
    candidate survives exactly when the lint-side estimate fits, and
    the heuristic's choice fits it too (or sits on the tile floors)."""
    from analytics_zoo_tpu.analysis.device import footprint_module
    from analytics_zoo_tpu.ops.pallas.common import (LANES, SUBLANES,
                                                     round_up,
                                                     vmem_usable_bytes)
    from analytics_zoo_tpu.ops.pallas.cross_entropy import (
        _ce_sweep_candidates, select_ce_blocks)

    lint = footprint_module()
    assert lint is not None
    budget = vmem_usable_bytes()
    for n, v, hidden, itemsize in ((32768, 8192, 512, 2),
                                   (4096, 32000, 4096, 2),
                                   (1000, 130, 24, 4)):
        dt = jnp.bfloat16 if itemsize == 2 else jnp.float32
        heuristic = select_ce_blocks(n, v, hidden, dt, bwd=True)
        bn, bv = heuristic
        assert bn % SUBLANES == 0 and bv % LANES == 0
        assert (lint.ce_bwd_vmem_bytes(
                    bn, bv, round_up(hidden, LANES), itemsize, True)
                <= budget or (bn, bv) == (SUBLANES, LANES))
        kept = _ce_sweep_candidates(n, v, hidden, itemsize, True,
                                    heuristic)
        if kept == [heuristic]:
            continue    # nothing fit: the heuristic-fallback contract
        for cand in kept:
            assert lint.ce_bwd_vmem_bytes(
                *cand, hidden=round_up(hidden, LANES),
                itemsize=itemsize, has_bias=True) <= budget
    # the bwd formula prices ABOVE the forward's at equal blocks (it
    # carries the (H, block_v) dW accumulator the forward doesn't)
    assert lint.ce_bwd_vmem_bytes(256, 512, 512, 2) \
        > lint.ce_vmem_bytes(256, 512, 512, 2)


# ---------------------------------------------------------------------------
# several devices: Mosaic calls must sit inside a shard_map
# ---------------------------------------------------------------------------

def _tpu_lowering_kernels(fn, *avals):
    """Kernel names of the Mosaic custom calls when ``fn`` is lowered FOR
    the TPU from here (no chip needed: only the front end runs)."""
    import re
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def test_flash_lowers_for_tpu_on_a_data_parallel_mesh():
    """jax refuses to lower a bare Mosaic kernel inside a jit that spans
    several devices ("cannot be automatically partitioned" — what a
    four-chip v5e host answered the default data mesh with, PR 21); the
    public entry runs it per data shard under shard_map instead."""
    from analytics_zoo_tpu.common.context import init_zoo_context
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    mesh = init_zoo_context().mesh                      # {data: 8}
    bsh = mesh_lib.batch_sharding(mesh)
    q = jax.ShapeDtypeStruct((8, 256, 2, 64), jnp.bfloat16, sharding=bsh)
    keep = jax.ShapeDtypeStruct((8, 256), jnp.float32, sharding=bsh)

    def grads(q, k, v, keep):
        return jax.grad(lambda q, k, v: jnp.sum(flash_entry(
            q, k, v, mask=keep, interpret=False).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    assert _tpu_lowering_kernels(grads, q, q, q, keep) == {
        "zoo_flash_fwd", "zoo_flash_bwd_dq", "zoo_flash_bwd_dkv"}


def test_fused_ce_kernels_lower_for_tpu_on_a_data_parallel_mesh():
    from analytics_zoo_tpu.common.context import init_zoo_context
    from analytics_zoo_tpu.ops.fused_cross_entropy import (
        fused_sparse_cross_entropy)
    from analytics_zoo_tpu.parallel import mesh as mesh_lib

    mesh = init_zoo_context().mesh
    h = jax.ShapeDtypeStruct((1024, 128), jnp.bfloat16,
                             sharding=mesh_lib.batch_sharding(mesh))
    w = jax.ShapeDtypeStruct((128, 1300), jnp.float32,
                             sharding=mesh_lib.replicated_sharding(mesh))
    b = jax.ShapeDtypeStruct((1300,), jnp.float32,
                             sharding=mesh_lib.replicated_sharding(mesh))
    y = jax.ShapeDtypeStruct((1024,), jnp.int32,
                             sharding=mesh_lib.batch_sharding(mesh))

    def grads(h, w, b, y):
        return jax.grad(lambda h, w, b: fused_sparse_cross_entropy(
            y, h, w, b, use_pallas=True, interpret=False),
            argnums=(0, 1, 2))(h, w, b)

    assert _tpu_lowering_kernels(grads, h, w, b, y) == {
        "zoo_ce_fwd", "zoo_ce_bwd_dh", "zoo_ce_bwd_dw"}
