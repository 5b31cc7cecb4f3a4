"""The experts' grouped products as Pallas TPU kernels (``zoo_moe_gmm*``):
what ``ops/grouped_matmul.py`` runs on a TPU in place of XLA's ``ragged-dot``
kernels.

``x`` (rows, d) holds group 0's rows first, then group 1's, and so on;
``group_sizes`` (G,) says how many each has, and their sum may be less than
``rows``. Three kernels, each in a plain form and a form for the SiLU-gated
pair of an expert's first two matrices:

* ``gmm`` / ``gated_gmm`` — rows x (G, d, h) -> rows x h. The grid walks a
  *visit list* computed from ``group_sizes`` in XLA and handed over as
  scalar-prefetched metadata: one visit a (group, row tile) pair that holds
  a row, in row order, so a tile that a group boundary cuts is visited once
  for each group in it, with a row mask on the store, and a group's weight
  block stays where it is from one visit to the next (no DMA while the
  group does not change). The visits past the last held row write the tail
  tiles as zeros and bring nothing in; what is left of the static grid does
  nothing. ``transpose_rhs`` reads ``w`` (G, h, d) through its index map and
  the product's dimension numbers (``dx`` of a plain product: no copy of the
  weights). The gated form takes the rows once and both matrices, keeps two
  float32 accumulators and writes ``act = silu(gate) * up`` with ``gate`` and
  ``up`` rounded to the rows' dtype first, as the unfused composition rounds
  them; with ``residuals`` it writes the rounded ``gate`` and ``up`` too,
  which is what the backward keeps.
* ``gated_gmm_dx`` — the backward of the gated pair towards the rows:
  ``d_gate``, ``d_up`` formed from ``d_act`` and the kept ``gate``, ``up`` in
  the kernel's prologue (float32, rounded to the rows' dtype as autodiff
  rounds them), written out for the ``dW`` kernel, and ``dx = d_gate Wgate^T
  + d_up Wup^T`` in one accumulator. The contraction (the experts' width) is
  held whole.
* ``gmm_dw`` — ``dW_g = x_g^T dy_g`` in float32 for one or two ``dy`` that
  share ``x``: grid over (d, h) tiles with a group's visits the reduction,
  accumulated in the output block, which stays resident while the group
  does not change; a group with no rows gets one visit that writes zeros.

Operands reach the matrix unit in their own dtype (bf16 runs it at full
rate), accumulation is float32. Tiles come from ``select_gmm_tiles`` priced
by ``common.gmm_vmem_bytes``; a group's whole matrix resident asks for more
than Mosaic's default scoped VMEM, which each call does for itself.

Chip readings (TPU v5e, PR 31, ``scripts/gmm-sweep``: device time of one
call from the profiler; bf16, 32768 rows of which 16384 held in 8 groups,
the largest 78 % of them, 2304 x 896; the least time for one product over
the rows held is 0.343 ms at the matrix unit's peak): XLA's ``ragged-dot``
1.54 ms (rows x 896), 1.32 ms (rows x 2304), 1.87 / 1.81 ms (the ``dW``s),
14.15 ms for the nine products of a layer's step, 21.8 % of the matrix
unit. These kernels, 256-row tiles with contraction and output width
whole: gated forward keeping gate and up 0.979 ms (two products), down
forward 0.552, ``dx`` of down 0.467, gated ``dx`` 1.076 (two), ``dW`` of
the pair 0.899 (two), ``dW`` of down 0.424: 4.40 ms, 70.3 %. 512-row
tiles 4.65 ms, 128-row 4.46; the contraction cut to 768 costs 0.1-0.4 ms a
kernel (a group's matrix is then streamed again for every row tile).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES as _LANES
from .common import VMEM_BYTES_DEFAULT as _VMEM_BYTES_DEFAULT
from .common import (gmm_vmem_bytes, pad_to_multiple, round_up,
                     vmem_budget_bytes)

__all__ = ["gmm", "gated_gmm", "gated_gmm_dx", "gmm_dw", "select_gmm_tiles",
           "visited_tile_rows"]

#: the row tile every kernel starts from, and the floor it is cut to where
#: it does not divide the rows. Swept on a v5e (PR 31, ``scripts/gmm-sweep``,
#: the nine products of the module docstring's shape): 128 rows 4.46 ms,
#: 256 4.40, 512 4.65, 1024 5.83: a smaller tile pads less at the group
#: boundaries, a larger one passes each weight tile fewer times
_PREFERRED_ROWS = 256
_ROW_FLOOR = 128

#: a grouped product may ask Mosaic for this many of the context's VMEM
#: budgets (``vmem_limit_bytes``): a v5e core has 128 MiB, of which the
#: default scoped limit is 16
_VMEM_BUDGETS = 4

#: abstract signature -> tiles, resolved once per process
_TILE_CACHE: dict = {}


class _Tiles(NamedTuple):
    """``tm`` rows, ``tk`` of the contraction (``dw``: of x's width) and
    ``tn`` of the output's width (``dw``: of dy's) a grid step holds."""
    tm: int
    tk: int
    tn: int


def _divisors(n: int):
    """The multiples of the lane tile that divide ``n``, largest first."""
    return [t for t in range(n, 0, -_LANES) if n % t == 0]


def _row_tile(rows: int) -> int:
    """The row tile of a buffer of ``rows``, which the calls pad to a whole
    number of it."""
    rows = round_up(rows, _ROW_FLOOR)
    tm = _PREFERRED_ROWS
    while tm > _ROW_FLOOR and rows % tm:
        tm //= 2
    return tm


def _tile_budget() -> int:
    """What a call's windows may take: half of what it may ask Mosaic for."""
    return _VMEM_BUDGETS * vmem_budget_bytes() // 2


def select_gmm_tiles(kernel: str, rows: int, k: int, n: int, itemsize: int,
                     pair: bool = False, residuals: bool = False,
                     budget_bytes: Optional[int] = None) -> _Tiles:
    """``(tm, tk, tn)`` of one grouped-product kernel (``"fwd"``, ``"dx"``:
    the gated backward, ``"dw"``) at ``rows x k -> n`` (``dw``: x is ``rows
    x k``, dy ``rows x n``): the preferred row tile cut to divide the rows
    (they are padded to the floor tile), ``k`` and ``n`` whole, then the
    larger of the two halved to its next divisor on the lane floor until
    ``gmm_vmem_bytes`` fits the budget, the row tile last. Deterministic in
    its arguments, so the jit cache is stable."""
    budget = budget_bytes if budget_bytes is not None else _tile_budget()
    tm = _row_tile(rows)
    ks, ns = _divisors(k), _divisors(n)
    if kernel == "dx":
        ks = ks[:1]             # the prologue's outputs are k wide, whole
    ki = ni = 0

    def over():
        return gmm_vmem_bytes(tm, ks[ki], ns[ni], itemsize, kernel=kernel,
                              pair=pair, residuals=residuals) > budget
    while over():
        if ki + 1 < len(ks) and (ks[ki] >= ns[ni] or ni + 1 == len(ns)):
            ki += 1
        elif ni + 1 < len(ns):
            ni += 1
        elif tm > _ROW_FLOOR:
            tm //= 2
        else:
            break
    return _Tiles(tm, ks[ki], ns[ni])


def _record_tile_choice(kernel: str, sig: str, tiles: _Tiles) -> None:
    try:
        from ...observability import default_registry
        # sig/choice are bounded by the distinct abstract kernel
        # signatures a process compiles (each also a jit cache entry)
        default_registry().gauge(  # zoolint: disable=ZL015 bounded label set
            "zoo_pallas_block_choice",
            "selected pallas kernel block sizes and resident major "
            "windows per abstract signature (1 = active choice)",
            labels={"kernel": "grouped_matmul", "sig": sig,
                    "choice": f"{kernel}={tiles.tm}x{tiles.tk}x{tiles.tn}"}
        ).set(1)
    # metrics must never break the compute path
    except Exception:  # zoolint: disable=ZL007
        pass


def _auto_tiles(kernel: str, rows: int, k: int, n: int, dtype,
                pair: bool = False, residuals: bool = False) -> _Tiles:
    """Cached per-signature tiles, named once per signature at trace time
    in ``zoo_pallas_block_choice{kernel="grouped_matmul"}``."""
    dt = jnp.dtype(dtype)
    budget = _tile_budget()
    sig = (budget, kernel, rows, k, n, dt.name, pair, residuals)
    tiles = _TILE_CACHE.get(sig)
    if tiles is None:
        tiles = _TILE_CACHE[sig] = select_gmm_tiles(
            kernel, rows, k, n, dt.itemsize, pair, residuals, budget)
        _record_tile_choice(
            kernel, f"{kernel}r{rows}k{k}n{n}{dt.name}"
            f"{'p' if pair else ''}{'r' if residuals else ''}", tiles)
    return tiles


def _compiler_params(est: int, semantics):
    """Mosaic's scoped-VMEM default holds a call whose estimate is under
    half of it; any other asks for twice its estimate."""
    limit = None if 2 * est <= _VMEM_BYTES_DEFAULT else 2 * est
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


# ---------------------------------------------------------------------------
# the visit list
# ---------------------------------------------------------------------------

def _group_tiles(group_sizes, tm: int):
    """``(ends, first, n_tiles)`` by group: the row after its last, the row
    tile its first row lies in, and how many row tiles hold a row of it."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    return ends, first, jnp.where(sizes > 0, (ends + tm - 1) // tm - first, 0)


@functools.partial(jax.jit, static_argnums=(1, 2, 3), inline=True)
def _visits(group_sizes, rows: int, tm: int, empty_groups: bool):
    """The scalar-prefetched metadata of a call over ``rows`` (a whole
    number of ``tm``-row tiles): ``(offsets (G+1,), group (S,), tile (S,),
    out_tile (S,), counts (2,))`` int32, ``S = rows / tm + G - 1`` grid
    steps. Step ``s < counts[0]`` is a visit: rows of group ``group[s]`` in
    row tile ``tile[s]`` (``empty_groups``: a group with no rows gets one
    visit all the same). A step from there on repeats the last visit's
    group and tile, so nothing is brought in for it. ``out_tile`` is the
    row tile a step writes: the visit's own, then for ``counts[0] <= s <
    counts[1]`` the tiles past the last held row (written as zeros), then
    the last tile again (left as it is)."""
    g = group_sizes.shape[0]
    tiles = rows // tm
    ends, first, n_tiles = _group_tiles(group_sizes, tm)
    first = jnp.minimum(first, tiles - 1)   # an empty group at the very end
    if empty_groups:
        n_tiles = jnp.maximum(n_tiles, 1)
    upto = jnp.cumsum(n_tiles)
    n_visits = upto[-1]
    step = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(upto[None, :] <= step[:, None], axis=1),
                        g - 1).astype(jnp.int32)
    tile = first[group] + step - (upto[group] - n_tiles[group])
    live = step < n_visits
    last = jnp.maximum(n_visits - 1, 0)
    group = jnp.where(live, group, group[last])
    tile = jnp.clip(jnp.where(live, tile, tile[last]), 0, tiles - 1)
    tail = (ends[-1] + tm - 1) // tm
    out_tile = jnp.where(live, tile,
                         jnp.minimum(tail + step - n_visits, tiles - 1))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    counts = jnp.stack([n_visits, n_visits + tiles - tail])
    return (offsets, group, tile.astype(jnp.int32),
            out_tile.astype(jnp.int32), counts.astype(jnp.int32))


def visited_tile_rows(group_sizes, rows: int) -> jax.Array:
    """Rows of the row tiles the forward kernels visit for these group
    sizes in a buffer of ``rows``: visits x tile rows. The rows held over
    this is the share of the kernels' work that is not padding at group
    boundaries."""
    tm = _row_tile(rows)
    return tm * jnp.sum(_group_tiles(group_sizes, tm)[2])


def _step_geometry(offsets, group, tile, s, tm: int):
    """``(lo, hi, whole)`` of visit ``s``: the rows of its tile that its
    group holds, as offsets into the tile, and whether that is all of
    them."""
    g = group[s]
    base = tile[s] * tm
    lo = jnp.maximum(offsets[g] - base, 0)
    hi = jnp.minimum(offsets[g + 1] - base, tm)
    return lo, hi, jnp.logical_and(lo == 0, hi == tm)


def _in_rows(shape, lo, hi):
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(row >= lo, row < hi)


def _store_rows(out_refs, values, s, out_tile, lo, hi, whole):
    """Write a visit's rows to each output: a tile the group holds whole is
    stored as it is; a cut one keeps the rows that earlier visits of the
    same tile wrote (the output block stays resident between them) and is
    zero elsewhere on its first visit, which is also what leaves the rows
    past the last group zero."""
    values = [v.astype(out.dtype) for out, v in zip(out_refs, values)]

    @pl.when(whole)
    def _():
        for out, value in zip(out_refs, values):
            out[...] = value

    @pl.when(jnp.logical_not(whole))
    def _():
        fresh = jnp.logical_or(
            s == 0, out_tile[jnp.maximum(s - 1, 0)] != out_tile[s])
        # rows under `kept` are what earlier visits wrote: none on the
        # first visit of a tile
        kept = jnp.where(fresh, 0, out_refs[0].shape[0])
        for out, value in zip(out_refs, values):
            before = jnp.where(_in_rows(value.shape, 0, kept), out[...],
                               jnp.zeros_like(value))
            out[...] = jnp.where(_in_rows(value.shape, lo, hi), value,
                                 before)


def _dot(a, b, transpose_b: bool):
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# rows x (G, k, n) -> rows x n
# ---------------------------------------------------------------------------

def _rows_kernel(offsets, group, tile, out_tile, counts, *refs, tm: int,
                 k_steps: int, pair: bool, residuals: bool,
                 transpose_rhs: bool):
    """One grid step (n tile, visit, k tile) of ``gmm`` / ``gated_gmm``."""
    n_w = 2 if pair else 1
    x_ref, w_refs = refs[0], refs[1:1 + n_w]
    n_out = 3 if pair and residuals else 1
    out_refs = refs[1 + n_w:1 + n_w + n_out]
    acc_refs = refs[1 + n_w + n_out:]
    s, kk = pl.program_id(1), pl.program_id(2)

    def store(sums):
        lo, hi, whole = _step_geometry(offsets, group, tile, s, tm)
        if pair:
            dt = out_refs[0].dtype
            gate, up = (t.astype(dt) for t in sums)
            gate32 = gate.astype(jnp.float32)
            act = gate32 * jax.nn.sigmoid(gate32) * up.astype(jnp.float32)
            sums = (act, gate, up)[:n_out]
        _store_rows(out_refs, sums, s, out_tile, lo, hi, whole)

    @pl.when(s < counts[0])
    def _():
        x = x_ref[...]
        parts = [_dot(x, w[...], transpose_rhs) for w in w_refs]
        if k_steps == 1:
            store(parts)
            return

        @pl.when(kk == 0)
        def _():
            for acc, part in zip(acc_refs, parts):
                acc[...] = part

        @pl.when(kk > 0)
        def _():
            for acc, part in zip(acc_refs, parts):
                acc[...] += part

        @pl.when(kk == k_steps - 1)
        def _():
            store([acc[...] for acc in acc_refs])

    @pl.when(jnp.logical_and(
        jnp.logical_and(s >= counts[0], s < counts[1]),
        kk == k_steps - 1))
    def _():
        for out in out_refs:
            out[...] = jnp.zeros_like(out)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6), inline=True)
def _rows_call(x, ws, group_sizes, tiles: _Tiles, residuals: bool,
               transpose_rhs: bool, interpret: bool):
    rows = x.shape[0]
    pair = len(ws) == 2
    k = x.shape[1]
    n = ws[0].shape[1 if transpose_rhs else 2]
    tm, tk, tn = tiles
    xr = pad_to_multiple(x, 0, tm)
    k_steps, n_steps = k // tk, n // tn
    meta = _visits(group_sizes, xr.shape[0], tm, False)
    steps = meta[1].shape[0]
    n_out = 3 if pair and residuals else 1

    def held(s, counts, kk):
        # past the visits a step repeats the last one's blocks: no DMA
        return jnp.where(s < counts[0], kk, k_steps - 1)

    x_spec = pl.BlockSpec(
        (tm, tk), lambda j, s, kk, off, grp, til, out, cnt:
        (til[s], held(s, cnt, kk)))
    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, s, kk, off, grp, til, out, cnt:
            (grp[s], j, held(s, cnt, kk)))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, s, kk, off, grp, til, out, cnt:
            (grp[s], held(s, cnt, kk), j))
    out_spec = pl.BlockSpec(
        (tm, tn), lambda j, s, kk, off, grp, til, out, cnt: (out[s], j))
    est = gmm_vmem_bytes(tm, tk, tn, x.dtype.itemsize, kernel="fwd",
                         pair=pair, residuals=residuals)
    name = ("zoo_moe_gmm_gated" if pair
            else "zoo_moe_gmm_dx" if transpose_rhs else "zoo_moe_gmm")
    res = pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, k_steps=k_steps, pair=pair,
                          residuals=residuals, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_steps, steps, k_steps),
            in_specs=[x_spec] + [w_spec] * len(ws),
            out_specs=[out_spec] * n_out,
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)] * len(ws)
                            if k_steps > 1 else [])),
        out_shape=[jax.ShapeDtypeStruct((xr.shape[0], n), x.dtype)] * n_out,
        compiler_params=_compiler_params(
            est, ("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(ws) * rows * k * n, transcendentals=0,
            bytes_accessed=(n_steps * rows * k + len(ws) * ws[0].size
                            + n_out * rows * n) * x.dtype.itemsize),
        interpret=interpret, name=name,
    )(*meta, xr, *ws)
    return tuple(r[:rows] for r in res)


def _tiles_for(tiles, kernel: str, rows: int, k: int, n: int, dtype,
               pair: bool = False, residuals: bool = False) -> _Tiles:
    """The tiles a call runs at: the selector's, or the caller's own (the
    tests', a sweep's), which have to divide widths on the lane floor."""
    for d in (k, n):
        if d % _LANES:
            raise ValueError(f"grouped product: width {d} is not a whole "
                             f"number of {_LANES}-lane tiles")
    if tiles is None:
        return _auto_tiles(kernel, rows, k, n, dtype, pair, residuals)
    tiles = _Tiles(*tiles)
    if k % tiles.tk or n % tiles.tn:
        raise ValueError(f"grouped product: tiles {tuple(tiles)} do not "
                         f"divide the widths ({k}, {n})")
    return tiles


def gmm(x, w, group_sizes, *, transpose_rhs: bool = False,
        tiles: Optional[Tuple[int, int, int]] = None,
        interpret: bool = False):
    """``(rows, k) x (G, k, n) -> (rows, n)`` by groups of rows, in ``x``'s
    dtype; rows past the groups come back zero. ``transpose_rhs``: ``w`` is
    ``(G, n, k)``."""
    tiles = _tiles_for(tiles, "fwd", x.shape[0], x.shape[1],
                       w.shape[1 if transpose_rhs else 2], x.dtype)
    return _rows_call(x, (w,), group_sizes, tiles, False, transpose_rhs,
                      interpret)[0]


def gated_gmm(x, wgate, wup, group_sizes, *, residuals: bool = False,
              tiles: Optional[Tuple[int, int, int]] = None,
              interpret: bool = False):
    """``act = silu(gate) * up`` of ``gate = x Wgate``, ``up = x Wup`` by
    groups of rows, each rounded to ``x``'s dtype. Returns ``(act,)``, or
    ``(act, gate, up)`` with ``residuals``."""
    tiles = _tiles_for(tiles, "fwd", x.shape[0], x.shape[1], wgate.shape[2],
                       x.dtype, True, residuals)
    return _rows_call(x, (wgate, wup), group_sizes, tiles, residuals, False,
                      interpret)


# ---------------------------------------------------------------------------
# the gated pair's backward towards the rows
# ---------------------------------------------------------------------------

def _gated_dx_kernel(offsets, group, tile, out_tile, counts, d_act_ref,
                     gate_ref, up_ref, wgate_ref, wup_ref, dx_ref,
                     d_gate_ref, d_up_ref, *, tm: int):
    s = pl.program_id(1)

    @pl.when(s < counts[0])
    def _():
        dt = d_gate_ref.dtype
        d_act = d_act_ref[...].astype(jnp.float32)
        gate = gate_ref[...].astype(jnp.float32)
        up = up_ref[...].astype(jnp.float32)
        sig = jax.nn.sigmoid(gate)
        # d silu(g) = sig (1 + g (1 - sig)), as autodiff of g * sig(g) has it
        d_gate = (d_act * up * (sig * (1.0 + gate * (1.0 - sig)))).astype(dt)
        d_up = (d_act * (gate * sig)).astype(dt)
        dx = (_dot(d_gate, wgate_ref[...], True)
              + _dot(d_up, wup_ref[...], True))
        lo, hi, whole = _step_geometry(offsets, group, tile, s, tm)
        _store_rows((dx_ref, d_gate_ref, d_up_ref), (dx, d_gate, d_up), s,
                    out_tile, lo, hi, whole)

    @pl.when(jnp.logical_and(s >= counts[0], s < counts[1]))
    def _():
        for out in (dx_ref, d_gate_ref, d_up_ref):
            out[...] = jnp.zeros_like(out)


@functools.partial(jax.jit, static_argnums=(6, 7), inline=True)
def _gated_dx_call(d_act, gate, up, wgate, wup, group_sizes, tiles: _Tiles,
                   interpret: bool):
    rows, h = d_act.shape
    d = wgate.shape[1]
    tm, _, tn = tiles
    ops = [pad_to_multiple(a, 0, tm) for a in (d_act, gate, up)]
    padded = ops[0].shape[0]
    meta = _visits(group_sizes, padded, tm, False)
    steps = meta[1].shape[0]
    n_steps = d // tn
    row_spec = pl.BlockSpec(
        (tm, h), lambda j, s, off, grp, til, out, cnt: (til[s], 0))
    w_spec = pl.BlockSpec(
        (None, tn, h), lambda j, s, off, grp, til, out, cnt: (grp[s], j, 0))
    dx_spec = pl.BlockSpec(
        (tm, tn), lambda j, s, off, grp, til, out, cnt: (out[s], j))
    d_spec = pl.BlockSpec(
        (tm, h), lambda j, s, off, grp, til, out, cnt: (out[s], 0))
    dt = d_act.dtype
    est = gmm_vmem_bytes(tm, h, tn, dt.itemsize, kernel="dx", pair=True)
    dx, d_gate, d_up = pl.pallas_call(
        functools.partial(_gated_dx_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n_steps, steps),
            in_specs=[row_spec] * 3 + [w_spec] * 2,
            out_specs=[dx_spec, d_spec, d_spec]),
        out_shape=[jax.ShapeDtypeStruct((padded, d), dt),
                   jax.ShapeDtypeStruct((padded, h), dt),
                   jax.ShapeDtypeStruct((padded, h), dt)],
        compiler_params=_compiler_params(est, ("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * d * h, transcendentals=n_steps * rows * h,
            bytes_accessed=(n_steps * 5 * rows * h + 2 * wgate.size
                            + rows * d) * dt.itemsize),
        interpret=interpret, name="zoo_moe_gmm_dx_gated",
    )(*meta, *ops, wgate, wup)
    return dx[:rows], d_gate[:rows], d_up[:rows]


def gated_gmm_dx(d_act, gate, up, wgate, wup, group_sizes, *,
                 tiles: Optional[Tuple[int, int, int]] = None,
                 interpret: bool = False):
    """``(dx, d_gate, d_up)`` of ``act = silu(gate) * up``, ``gate = x
    Wgate``, ``up = x Wup`` given ``d_act`` and the kept ``gate``, ``up``:
    ``d_gate`` and ``d_up`` rounded to the rows' dtype, ``dx = d_gate
    Wgate^T + d_up Wup^T``; rows past the groups zero in all three."""
    tiles = _tiles_for(tiles, "dx", d_act.shape[0], d_act.shape[1],
                       wgate.shape[1], d_act.dtype, True)
    if tiles.tk != d_act.shape[1]:
        raise ValueError(f"gated dx: the contraction is held whole, not in "
                         f"tiles of {tiles.tk}")
    return _gated_dx_call(d_act, gate, up, wgate, wup, group_sizes, tiles,
                          interpret)


# ---------------------------------------------------------------------------
# dW_g = x_g^T dy_g
# ---------------------------------------------------------------------------

def _dw_kernel(offsets, group, tile, out_tile, counts, x_ref, *refs,
               tm: int):
    n = len(refs) // 2
    dy_refs, out_refs = refs[:n], refs[n:]
    s = pl.program_id(2)
    g = group[s]

    @pl.when(jnp.logical_or(s == 0, group[jnp.maximum(s - 1, 0)] != g))
    def _():
        for out in out_refs:
            out[...] = jnp.zeros_like(out)

    lo, hi, whole = _step_geometry(offsets, group, tile, s, tm)
    live = jnp.logical_and(s < counts[0], hi > lo)
    dims = (((0,), (0,)), ((), ()))

    def add(x, dys):
        for out, dy in zip(out_refs, dys):
            out[...] += jax.lax.dot_general(
                x, dy, dims, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, whole))
    def _():
        add(x_ref[...], [dy[...] for dy in dy_refs])

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _():
        # both sides: what stands in the rows past the groups is the
        # caller's, and 0 x inf is not 0
        x = x_ref[...]
        x = jnp.where(_in_rows(x.shape, lo, hi), x, jnp.zeros_like(x))
        add(x, [jnp.where(_in_rows(dy.shape, lo, hi), dy[...],
                          jnp.zeros(dy.shape, dy.dtype)) for dy in dy_refs])


@functools.partial(jax.jit, static_argnums=(3, 4), inline=True)
def _dw_call(x, dys, group_sizes, tiles: _Tiles, interpret: bool):
    rows, d = x.shape
    h = dys[0].shape[1]
    g = group_sizes.shape[0]
    tm, td, th = tiles
    xr = pad_to_multiple(x, 0, tm)
    dyr = [pad_to_multiple(dy, 0, tm) for dy in dys]
    meta = _visits(group_sizes, xr.shape[0], tm, True)
    steps = meta[1].shape[0]
    x_spec = pl.BlockSpec(
        (tm, td), lambda i, j, s, off, grp, til, out, cnt: (til[s], i))
    dy_spec = pl.BlockSpec(
        (tm, th), lambda i, j, s, off, grp, til, out, cnt: (til[s], j))
    out_spec = pl.BlockSpec(
        (None, td, th), lambda i, j, s, off, grp, til, out, cnt:
        (grp[s], i, j))
    est = gmm_vmem_bytes(tm, td, th, x.dtype.itemsize, kernel="dw",
                         pair=len(dys) == 2)
    return tuple(pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(d // td, h // th, steps),
            in_specs=[x_spec] + [dy_spec] * len(dys),
            out_specs=[out_spec] * len(dys)),
        out_shape=[jax.ShapeDtypeStruct((g, d, h), jnp.float32)] * len(dys),
        compiler_params=_compiler_params(
            est, ("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * len(dys) * rows * d * h, transcendentals=0,
            bytes_accessed=((h // th) * rows * d + (d // td) * len(dys)
                            * rows * h) * x.dtype.itemsize
            + len(dys) * g * d * h * 4),
        interpret=interpret, name="zoo_moe_gmm_dw",
    )(*meta, xr, *dyr))


def gmm_dw(x, dys, group_sizes, *,
           tiles: Optional[Tuple[int, int, int]] = None,
           interpret: bool = False):
    """``dW_g = x_g^T dy_g`` in float32, ``(G, d, h)``, for each ``dy`` of
    the tuple ``dys`` (one or two, sharing ``x``); zeros for a group with
    no rows."""
    tiles = _tiles_for(tiles, "dw", x.shape[0], x.shape[1], dys[0].shape[1],
                       x.dtype, len(dys) == 2)
    return _dw_call(x, tuple(dys), group_sizes, tiles, interpret)
