"""Flash attention — the L0 Pallas TPU kernel behind the attention stack
(SURVEY §2.3; the reference has no custom kernels at all — its attention is
whole-matrix softmax inside ``TransformerLayer.scala:56``/``BERT.scala:66``,
materializing the (T, T) score matrix in HBM).

Schedule (all three kernels): two-level tiling with the outer level
resident. A grid cell is one block of its own side (a q block for fwd and
dq, a k block for dkv) against a *major window* of the streamed side (K/V,
or q/dO): the whole sequence where the VMEM estimate lets it (T = 4096,
D = 64: K and V of a head are 512 KB each), else the fewest equal runs of
tiles that fit. The cell walks its window with an in-kernel loop over
``(block_q, block_k)`` compute tiles whose bounds come from ``program_id``:
up to the causal diagonal only (fwd, dq), from it on (dkv). So no grid step
is skipped inside a window, nothing is brought in for a tile that is not
computed, and K/V are read from VMEM, once per head, instead of streamed
from HBM once per q block. Where the window is not the whole sequence a
causal step wholly on the far side of the diagonal runs two empty loops and
repeats its neighbour's block index, which costs no DMA.

The loop is split at the tiles that need a mask: tiles crossed by the
causal diagonal, the tile the kv padding ends in, and every tile of a call
with a key-padding mask build the keep-mask (two iotas, compares, a select)
and guard the running max; interior tiles run ``exp(s - m)`` bare. Both
matmuls hit the MXU in the input dtype (bfloat16 operands run it at full
rate; accumulation is always float32), the online-softmax carry (acc/m/l)
lives in VMEM scratch across tiles and windows, and ``1/sqrt(D)`` is folded
into the resident block once where it is a power of two (D = 64: 0.125,
exact in bf16, bit-identical to scaling the scores).

Row statistics (the forward's log-sum-exp, the backward's ``delta``) are
one float a row, ``f32[B*H, 1, T]`` with q along lanes: what the step keeps
per layer for the backward is T floats a head. The forward reduces along
lanes, so it stands its column of statistics down into a row once per q
block (select-and-sum against an identity pattern: exact, no transpose);
dq stands lse back up once per q block (and lays its own ``delta`` down
for dkv the same way); dkv forms its tile transposed,
``s^T = k q^T`` of shape (block_k, block_q), so the (1, block_q) statistics
broadcast over sublanes as stored and ``dV += p^T dO``, ``dK += ds^T q``
are plain products.

Causal masking is BOTTOM-RIGHT aligned like the XLA oracle
(``ops/attention.py:41``): query i attends keys ``j <= i + (t_kv - t_q)``.
Rows with no visible key (t_q > t_kv tails, or fully-masked rows) return
zeros — the one spot the oracle differs (its -1e9 fill degrades to uniform
weights there). An optional per-batch key-padding mask (B, Tk) rides in the
major window as one (1, block_k) row per tile — the BERT ``attention_mask``
path. In dkv a hidden or padded key only ever touches its own rows of
dk/dv, so that kernel masks nothing but the diagonal and the wrapper drops
those rows.

Heads in place (PR 37): the entry takes q ``(B, T, Hq, D)`` and k, v
``(B, T, Hkv, D)``, what a projection's ``(B, T, H*D)`` output reshapes to
for nothing, and gives ``(B, T, Hq, D)``. A Mosaic call pins its operands'
layout, so the ``(B*H, T, D)`` operands the kernels used to take cost a
materialised transpose of q, k, v, dO on the way in and of o, dq, dk, dv on
the way out: eleven copies a GPT-1 layer, twelve a latent one, 5-8 % of a
step. Now the arrays stay ``(B, T, H*D)`` and the BlockSpec index maps
find a head in them; what a grid cell holds is chosen from the head width
and the head counts alone (``_head_layout``), one set of kernel bodies:

* ``inplace``, D a multiple of 128 (whole lane tiles): the grid's first
  axis still counts ``b*h``, and a block that was ``(1, block, D)`` at
  ``(bh, i, 0)`` is the same block at ``(bh // H, i, bh % H)``. The bodies
  see what they saw.
* ``pair``, D = 64 with even head counts and the query heads of a pair on
  one key/value head or on a pair of their own (``group`` 1 or even): a
  block is ``(1, block, 128)``, two neighbouring heads, and the grid's
  first axis counts pairs. K/V of the pair are resident together: the same
  bytes a head, and lane-dense where a lone 64-wide head filled half of
  every tile it moved or stored. Inside a cell each head runs the tile
  loop it ran before, on the two-head blocks as they are: the other
  head's 64 lanes of the RESIDENT block are zeroed once a cell (q in fwd
  and dq, with dO; k and v in dkv), so a contraction over all 128 lanes is
  that head's score tile exactly, at the matrix-unit passes a 64-deep
  contraction costs anyway, and ``p @ v`` (``ds @ k``, ``p^T dO``,
  ``ds^T q``) comes out 128 wide with that head's result on its own half
  and another head's on the other, which the cell drops when it puts the
  two accumulators' halves side by side. With grouped heads the two query
  heads of a pair share ONE key/value head, which stands in either half
  of ITS pair's block (``_Heads.kv_half``): the resident block's half is
  rotated onto that half (64 lanes, through float32: Mosaic rotates
  32-bit lanes only) and the result rotated back; in dkv all the query
  heads of a step belong to one key head, the first half of the steps to
  the block's first, and the accumulators are folded onto its lanes at
  the end of either half.
* ``split``, everything else (the tests' D = 8; odd head counts at
  D = 64): the wrapper transposes to ``(B*H, T, 1*D)``, the in-place
  geometry with one head a row.

Row statistics stay ``f32[B*H, 1, T]`` in every layout (a pair cell takes
two rows of them). The callers keep their side of the bargain: on a TPU a
``(B, T, H, D)`` VIEW of a ``(B, T, H*D)`` array is another tiling of the
same bytes, so any XLA operation on such a view costs a copy of the tensor
(XLA then prefers T-minor layouts and copies on both sides of them). The
layers therefore rotate q and k in place (``ops.attention.
apply_rotary_in_place``), the latent layer writes k and v where a head
belongs through slices of its weight, and ``delta = rowsum(dO * O)``, the
one per-head reduction the backward needs, is taken inside the dq kernel
from the dO block it holds and the O block beside it, and handed on to dkv
as a second output.

Grouped heads and windows (PR 28): q may have ``group`` times the heads of
k and v. The K/V block specs of fwd and dq index cell ``c // group``, so
nothing is repeated in HBM and the query heads of a group find their K/V
block resident; dkv's grid counts key/value heads and walks the major
windows of each of the group's query heads in turn into one pair of
accumulators. A causal ``window`` (``i - window < j <= i``) bounds the tile
loops on BOTH sides (``_k_tile_bands``, ``_q_tile_bands``): tiles behind
the window are never computed or brought in, and the mask is built on the
band the diagonal cuts and on the band the window's trailing edge cuts,
nowhere between. Window calls carry ``_win`` behind the kernel's name.
With ``group == 1`` and no window every kernel is traced as before.

Backward: the standard two-kernel recompute scheme (no (T, T) tensor is ever
materialized, unlike the r3 XLA-recompute fallback this replaces): the dq
kernel (grid bh, q block, K/V window), which also takes ``delta =
rowsum(dO·O)`` once a q block, and the dk/dv kernel (grid bh, k block, q
window), each re-forming one probability tile at a time from the saved
log-sum-exp. Memory stays
O(block²) end to end, which is what makes long-context *training* fit.

What a rematerialising caller keeps (PR 35): the backward needs ``(q, k,
v, mask, out, lse)``. Under a ``jax.checkpoint`` q, k and v come back from
the projections, which are recomputed for their own gradients anyway; out
and lse can only come from the forward kernel, the most expensive call of
a block. So the forward rule names the two (``FLASH_SAVED``:
``zoo_flash_out``, ``zoo_flash_lse``, by ``checkpoint_name``) and hands
the named values on as output and residuals. A checkpoint whose policy is
``save_only_these_names(*FLASH_SAVED)`` (``DecoderStack(remat=True)``)
keeps them, one tensor of the output's size and one float a row, and its
backward pass holds no forward kernel; every other policy
(``zoo.train.remat``, ``GPipe(remat=)``, the routed layer's chunks) and a
gradient outside any checkpoint see an identity that lowers to nothing.
``saved_bytes_log`` tells such a caller at trace time what it keeps.

Chip readings (TPU v5e, PR 27, ``scripts/flash-sweep``: device time of one
call from the profiler, causal bf16 D = 64, B*H x T = 393216 rows): at
T = 4096 fwd 9.86 -> 4.02 ms, dq 8.74 -> 4.43 ms, dkv 11.87 -> 5.57 ms
against the streamed (256, 512) schedule this replaced, 15.5 -> 33.6 % of
the three kernels' roofline (``benchmark/lib/kernel_cost.py``); T = 2048
17.27 -> 8.43 ms a call triple (13.6 -> 27.9 %); T = 8192 56.9 -> 25.0 ms
(16.5 -> 37.7 %). D = 64 half-fills the MXU's depth (QK^T, dO V^T) or width
(PV, dV, dK, dQ) in every product, so about 50 % is this head size's
ceiling.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES as _LANES
from .common import SUBLANES as _SUBLANES
from .common import ATTENTION_FWD_K_TILES as _FWD_K_TILES
from .common import (attention_budget_scale, attention_vmem_bytes,
                     pad_to_multiple, sweep_fastest, vmem_budget_bytes,
                     vmem_usable_bytes)
from .common import round_up as _round_up

__all__ = ["FLASH_SAVED", "flash_attention", "saved_bytes_log",
           "select_attention_blocks"]

#: the names (``jax.ad_checkpoint.checkpoint_name``) the forward rule gives
#: the kernel's output and its row statistics, the two residuals only the
#: forward kernel can make again. A ``jax.checkpoint`` whose policy is
#: ``save_only_these_names(*FLASH_SAVED)`` keeps them, and its backward
#: pass recomputes q, k and v but not the kernel; to every other policy,
#: and outside a checkpoint, a name is an identity that lowers to nothing
FLASH_SAVED = ("zoo_flash_out", "zoo_flash_lse")

#: where ``saved_bytes_log`` is open: name -> bytes, added to as forward
#: rules are traced
_SAVED_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "zoo_flash_saved_log", default=None)


@contextlib.contextmanager
def saved_bytes_log():
    """A dict that every forward rule traced inside the block adds the
    bytes of its two named residuals to, by name (``FLASH_SAVED``): how a
    rematerialising caller learns at trace time what its checkpoints keep
    (``DecoderStack``). Empty where no kernel was differentiated: the XLA
    op ran, or nothing took a gradient."""
    log: dict = {}
    token = _SAVED_LOG.set(log)
    try:
        yield log
    finally:
        _SAVED_LOG.reset(token)


def _saved(x, name: str):
    """``x`` under ``name``, its bytes added to the open log."""
    log = _SAVED_LOG.get()
    if log is not None:
        log[name] = log.get(name, 0) + x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


# ---------------------------------------------------------------------------
# block autotuning: VMEM-budget heuristic + optional one-shot on-device sweep
# (the footprint formula itself is the SHARED estimator in common.py —
# cross_entropy's clamp and zoolint's static ZL024 check price with the
# same function, property-tested in tests/test_pallas.py)
# ---------------------------------------------------------------------------

#: preferred default ``(block_q, block_k)`` of dq and dkv; the forward takes
#: ``_FWD_K_TILES`` k tiles as one. Swept on a v5e for this schedule (PR 27,
#: ``scripts/flash-sweep``, causal bf16 D=64, ms per fwd+dq+dkv call triple
#: at T = 2048 / 4096 / 8192, 393216 rows): (512, 512) 8.43 / 14.01 / 24.98;
#: (1024, 512) 8.96 / 14.17 / 25.25; (512, 1024) 10.02 / 15.21 / 26.07;
#: (256, 512) 10.21 / 17.04 / 30.72; (512, 256) 10.38 / 17.64 / 32.15;
#: (256, 256) 12.98 / 22.86 / 42.65. The same pair with the forward NOT
#: widened: 9.36 / 16.03 / 29.17 (its forward 3.56 / 6.04 / 10.99 ms against
#: 2.62 / 4.02 / 6.81): the forward's row reductions and carry rescale grow
#: with block_q alone. A (1024, 1024) forward is 1.5-3 % better still and
#: does not fit the budget at T = 8192
_PREFERRED_BLOCKS = (512, 512)

#: abstract signature -> _Schedule, resolved once per process
_BLOCK_CACHE: dict = {}

#: back-compat aliases — the estimator and budget constants moved to
#: ``common.py`` so the fused-CE clamp and the zoolint device pass share
#: one formula
_kernel_vmem_bytes = attention_vmem_bytes
from .common import VMEM_BYTES_DEFAULT as _VMEM_BYTES_DEFAULT  # noqa: E402
from .common import VMEM_USABLE_FRACTION as _VMEM_USABLE_FRACTION  # noqa: E402


#: half a lane tile: the head width two of which share a block
_HALF = _LANES // 2


class _Heads(NamedTuple):
    """Where a call's kernels find a head (the module docstring's "Heads in
    place"). ``layout``: ``inplace`` (operands ``(B, T, H*D)``, a block one
    head of whole lane tiles), ``pair`` (the same arrays at D = 64, a block
    two neighbouring heads) or ``split`` (operands ``(B*H, T, D)``, the
    wrapper transposes). ``cells`` / ``kv_cells``: grid cells a batch row
    on the query and on the key/value side (heads, or pairs of them);
    ``group`` query cells share a key/value cell (heads to a head, and so
    pairs to a pair); ``width`` lanes of a block; a cell runs ``per_cell``
    heads."""
    layout: str
    cells: int
    kv_cells: int
    group: int
    width: int
    per_cell: int

    def row_and_block(self, cell, cells: int):
        """(array row, lane block) of grid cell ``cell``, ``cells`` of
        them a batch row."""
        if self.layout == "split":
            return cell, 0
        return cell // cells, cell % cells

    def kv_half(self, hh: int, q_cell):
        """Which half of its key/value block query head ``hh`` of pair
        ``q_cell`` attends: its own without grouped heads, else the half
        the pair's ONE key/value head stands on. None outside a pair."""
        if self.per_cell == 1:
            return None
        if self.group == 1:
            return hh
        return (2 * (q_cell % self.cells)) // self.group % 2


def _head_layout(h: int, h_kv: int, d: int) -> _Heads:
    """The layout a call takes, from what it can see: head width and head
    counts. Whole lane tiles: in place. Half a tile: pairs, where both
    sides count an even number of heads and a pair of query heads shares
    one key/value head or has a pair of its own (``group`` 1 or even).
    Everything else: the wrapper splits the heads out."""
    group = h // h_kv
    if d % _LANES == 0:
        return _Heads("inplace", h, h_kv, group, d, 1)
    if d == _HALF and h_kv % 2 == 0 and (group == 1 or group % 2 == 0):
        return _Heads("pair", h // 2, h_kv // 2, group, _LANES, 2)
    return _Heads("split", h, h_kv, group, d, 1)


def _onto(x, src, dst):
    """Half ``src`` of ``x``'s 128 lanes standing on half ``dst``, zeros on
    the other half: how a pair cell cuts one head out of a two-head block
    and lines it up with the half the other operand keeps it on. Either
    may be a traced scalar (a grouped pair's key/value half)."""
    half = (jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
            >= _HALF).astype(jnp.int32)
    x = jnp.where(half == src, x, jnp.zeros_like(x))
    if isinstance(src, int) and isinstance(dst, int) and src == dst:
        return x
    # Mosaic rotates 32-bit lanes only; once a cell, not once a tile
    swapped = pltpu.roll(x.astype(jnp.float32), _HALF, x.ndim - 1
                         ).astype(x.dtype)
    if isinstance(src, int) and isinstance(dst, int):
        return swapped
    return jnp.where(src == dst, x, swapped)


class _Tiling(NamedTuple):
    """How one kernel cuts the (q, k) plane: the ``(block_q, block_k)``
    compute tile, and ``major``, the rows of the streamed side each grid
    cell holds resident and walks tile by tile (K/V rows for fwd and dq,
    whose cell is a q block; q/dO rows for dkv, whose cell is a k block).
    A major window is a whole number of tiles; the kernel pads its
    streamed side to a whole number of major windows."""
    block_q: int
    block_k: int
    major: int


class _Schedule(NamedTuple):
    fwd: _Tiling
    dq: _Tiling
    dkv: _Tiling


def select_attention_blocks(t_q: int, t_kv: int, d: int, dtype,
                            causal: bool = False, has_mask: bool = False,
                            budget_bytes: Optional[int] = None,
                            heads_per_cell: int = 1):
    """VMEM-budget-aware (block_q, block_k): start from the swept
    ``_PREFERRED_BLOCKS``, clamp to the sequence lengths, then shrink the
    larger block until the largest of the three kernels' estimated
    footprints at that pair (tile, accumulators, a one-tile window) fits
    the budget. Deterministic — a pure function of the abstract signature,
    so the jit cache is stable. ``_resolve_schedule`` turns the pair into
    the kernels' tilings and major windows. Without an explicit budget a
    head wider than a lane tile is fitted into ``attention_budget_scale``
    budgets (D = 256: (512, 512) again, where one budget gave (256, 512))."""
    budget = budget_bytes if budget_bytes is not None else \
        vmem_usable_bytes() * attention_budget_scale(d)
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk = _PREFERRED_BLOCKS
    bq = max(_SUBLANES, min(bq, _round_up(max(t_q, 1), _SUBLANES)))
    bk = max(_LANES, min(bk, _round_up(max(t_kv, 1), _LANES)))
    # every shrink step rounds DOWN to the tile floor — halving an
    # already-clamped odd block (bq 56 -> 28, or 200 -> 100) would hand
    # Mosaic an untileable pair on the default path every caller hits
    while (_kernel_vmem_bytes(bq, bk, d, itemsize, has_mask,
                              heads=heads_per_cell) > budget
           and (bq > _SUBLANES or bk > _LANES)):
        if bk >= 2 * bq and bk > _LANES:
            bk = max(_LANES, bk // 2 // _LANES * _LANES)
        elif bq > _SUBLANES:
            bq = max(_SUBLANES, bq // 2 // _SUBLANES * _SUBLANES)
        else:
            bk = max(_LANES, bk // 2 // _LANES * _LANES)
    return bq, bk


#: what ``zoo.pallas.block_sweep`` times beside the heuristic's choice
_SWEEP_PAIRS = (_PREFERRED_BLOCKS, (1024, 512), (512, 1024), (256, 512),
                (512, 256), (256, 256))


def _sweep_candidates(t_q: int, t_kv: int, d: int, itemsize: int,
                      has_mask: bool, heuristic):
    budget = vmem_usable_bytes() * attention_budget_scale(d)
    out = []
    for bq, bk in (heuristic,) + _SWEEP_PAIRS:
        # clamp to the sequence lengths WITH the tile rounding the kernel
        # needs (a raw min() against an unaligned T yields untileable
        # pairs like (128, 1000) that can only fail to compile)
        cand = (max(_SUBLANES, min(bq, _round_up(max(t_q, 1), _SUBLANES))),
                max(_LANES, min(bk, _round_up(max(t_kv, 1), _LANES))))
        if cand in out:
            continue
        if _kernel_vmem_bytes(*cand, d=d, itemsize=itemsize,
                              has_mask=has_mask) <= budget:
            out.append(cand)
    return out or [heuristic]


def _time_blocks(b, h, t_q, t_kv, d, dtype, causal, has_mask, block_q,
                 block_k, repeats: int = 2) -> float:
    """Best-of-``repeats`` wall seconds for one compiled fwd+bwd of the
    kernel at the given blocks, on synthetic on-device operands. Masked
    signatures time the MASKED kernel — the winner is cached per
    signature (has_mask included), so it must be measured on the kernel
    that signature will actually run."""
    import time

    import numpy as np
    rng = np.random.default_rng(0)
    q = jax.device_put(jnp.asarray(
        rng.normal(size=(b, t_q, h, d)).astype(np.float32), dtype))
    k = jax.device_put(jnp.asarray(
        rng.normal(size=(b, t_kv, h, d)).astype(np.float32), dtype))
    v = jax.device_put(jnp.asarray(
        rng.normal(size=(b, t_kv, h, d)).astype(np.float32), dtype))
    m = (jax.device_put(jnp.ones((b, t_kv), jnp.float32))
         if has_mask else None)
    sched = _resolve_schedule(t_q, t_kv, d, dtype, has_mask, block_q,
                              block_k)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q: jnp.sum(
            _flash(q, k, v, m, causal, sched, False, None)
            .astype(jnp.float32)))(q)

    fn = jax.jit(fwd_bwd)
    jax.block_until_ready(fn(q, k, v))      # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(q, k, v))
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep_blocks(b, h, t_q, t_kv, d, dtype, causal, has_mask, heuristic,
                  timer=None):
    """``zoo.pallas.block_sweep``: one-shot on-device sweep over the
    candidate block pairs, winner cached per abstract signature. ``timer``
    is injectable for tests; the default times a real compiled fwd+bwd."""
    timer = timer or (lambda bq, bk: _time_blocks(
        b, h, t_q, t_kv, d, dtype, causal, has_mask, bq, bk))
    return sweep_fastest(
        "flash", _sweep_candidates(t_q, t_kv, d, jnp.dtype(dtype).itemsize,
                                   has_mask, heuristic), timer)


def _choice_label(sched) -> str:
    """``fwd=512x1024/kmajor4096,...``: each kernel's tile and the rows of
    the streamed side its grid cell holds resident (q rows for dkv)."""
    return ",".join(
        f"{name}={t.block_q}x{t.block_k}/"
        f"{'q' if name == 'dkv' else 'k'}major{t.major}"
        for name, t in zip(sched._fields, sched))


def _record_block_choice(sig: str, sched, census: dict,
                         layout: str) -> None:
    try:
        from ...observability import default_registry
        reg = default_registry()
        # sig/choice are bounded by the distinct abstract kernel
        # signatures a process compiles (each also a jit cache entry)
        reg.gauge(  # zoolint: disable=ZL015 bounded label set
            "zoo_pallas_block_choice",
            "selected pallas kernel block sizes and resident major "
            "windows per abstract signature (1 = active choice)",
            labels={"kernel": "flash_attention", "sig": sig,
                    "choice": f"{_choice_label(sched)},heads={layout}"}
        ).set(1)
        for kind, n in census.items():
            reg.gauge(  # zoolint: disable=ZL015 bounded label set
                "zoo_pallas_flash_tiles",
                "static tile census of one (batch, head) of a flash "
                "forward call at this signature: compute tiles that run "
                "mask-free (interior), that build the mask (masked), grid "
                "steps with nothing to compute (skipped_steps) and, for a "
                "window call, causal tiles behind the window that are "
                "never computed (skipped_tiles)",
                labels={"sig": sig, "kind": kind}).set(n)
    # metrics must never break the compute path
    except Exception:  # zoolint: disable=ZL007
        pass


def _auto_blocks(q_shape, t_kv: int, dtype, causal: bool, has_mask: bool,
                 interpret: bool, window: Optional[int] = None,
                 group: int = 1) -> _Schedule:
    """Cached per-signature schedule: the VMEM heuristic's tile, optionally
    refined by the one-shot on-device sweep (compiled TPU runs only — the
    interpreter's timings say nothing about the MXU), and the major
    windows that tile gets. The heuristic is a pure function of
    (T, D, dtype, causal, mask), so its cache key drops batch/heads — a
    ragged final batch or an evaluate at a different B must not re-resolve
    (or worse, re-SWEEP: compiling and timing the candidates with live
    training state resident). Only sweep-timed entries key on the full
    shape, since wall time does scale with B·H. A window and grouped
    heads change the census and the label, not the tile: they join the
    key and the label only where a call has them."""
    b, t_q, h, d = q_shape
    dt = jnp.dtype(dtype)
    heads = _head_layout(h, h // group, d)
    layout, per = heads.layout, heads.per_cell
    from ...common.context import get_zoo_context
    sweep = (bool(get_zoo_context().get("zoo.pallas.block_sweep", False))
             and not interpret and jax.default_backend() == "tpu")
    # the live budget is part of the key — re-initializing the context
    # with zoo.pallas.vmem_budget_mb must take effect at the next call,
    # not silently keep blocks sized for the old budget
    budget = vmem_usable_bytes() * attention_budget_scale(d)
    base = (t_q, t_kv, d, dt.name, causal, has_mask, layout)
    if window is not None or group != 1:
        base += (window, group)
    sig = (budget, "sweep", b, h) + base if sweep else (budget,) + base
    cached = _BLOCK_CACHE.get(sig)
    if cached is not None:
        return cached
    choice = select_attention_blocks(t_q, t_kv, d, dt, causal=causal,
                                     has_mask=has_mask,
                                     budget_bytes=budget, heads_per_cell=per)
    if sweep:
        choice = _sweep_blocks(b, h, t_q, t_kv, d, dt, causal, has_mask,
                               choice)
    sched = _resolve_schedule(t_q, t_kv, d, dt, has_mask, *choice,
                              heads_per_cell=per)
    _BLOCK_CACHE[sig] = sched
    # the metric label mirrors the cache key: heuristic entries apply to
    # EVERY batch/head shape at this (T, D, dtype) signature, so baking
    # the first caller's b/h into the label would misdescribe the scope
    _record_block_choice(
        (f"b{b}h{h}" if sweep else "")
        + f"tq{t_q}tk{t_kv}d{d}{dt.name}"
        f"{'c' if causal else ''}{'m' if has_mask else ''}"
        + (f"w{window}" if window is not None else "")
        + (f"g{group}" if group != 1 else ""), sched,
        _tile_census(t_q, t_kv, sched.fwd, causal, has_mask, window),
        layout)
    return sched


def _resolve_schedule(t_q: int, t_kv: int, d: int, dtype, has_mask: bool,
                      block_q: int, block_k: int,
                      budget_bytes: Optional[int] = None,
                      heads_per_cell: int = 1) -> _Schedule:
    """The three kernels' tilings from one ``(block_q, block_k)``: dq and
    dkv take it, the forward takes ``_FWD_K_TILES`` k tiles as one. Each is
    clamped to the sequence (back ON the tile floors: a raw min() against
    an unaligned T hands Mosaic an untileable block; the padding absorbs
    the round-up and the kernels mask past t_q/t_kv) and then given the
    largest major window whose estimate the whole per-core budget holds
    (``select_attention_blocks`` fitted the tile into the usable half of
    it): the whole sequence where that fits, else the sequence cut into
    the fewest equal runs of tiles that do. Pure in its arguments and the
    context's budget. (A window call's dkv brings a whole q/dO window in
    at every grid step for three tiles' work; cutting that window to the
    rows a k block can see was tried and lost: 14.97 against 12.34 ms a
    call at window 1024 of 8192, four times the grid steps; chip run,
    PR 37.)"""
    budget = budget_bytes if budget_bytes is not None else \
        vmem_budget_bytes() * attention_budget_scale(d)
    itemsize = jnp.dtype(dtype).itemsize
    bq = _round_up(min(block_q, max(t_q, 1)), _SUBLANES)
    bk = _round_up(min(block_k, max(t_kv, 1)), _LANES)

    def tiling(kernel: str) -> _Tiling:
        wide = _FWD_K_TILES * bk if kernel == "fwd" else bk
        wide = min(wide, _round_up(max(t_kv, 1), _LANES))
        t, blk = (t_q, bq) if kernel == "dkv" else (t_kv, wide)
        n_tiles = -(-max(t, 1) // blk)
        tiles = 1
        for parts in range(1, n_tiles + 1):
            tiles = -(-n_tiles // parts)
            if _kernel_vmem_bytes(bq, wide, d, itemsize, has_mask,
                                  major=tiles * blk, kernel=kernel,
                                  heads=heads_per_cell) <= budget:
                break
        return _Tiling(bq, wide, tiles * blk)

    return _Schedule(*(tiling(kernel) for kernel in _Schedule._fields))


def _k_tile_range(qi, *, block_q, block_k, t_q, t_kv, causal, has_mask,
                  mn=min, mx=max):
    """``(n_full, hi)`` for q block ``qi`` (fwd and dq): k tiles
    ``[0, n_full)`` hold no masked element, ``[n_full, hi)`` do (the
    causal diagonal crosses them, the kv padding ends in them, or the call
    has a key-padding mask), and tiles from ``hi`` on hold no visible key.
    Plain arithmetic, so the kernels run it on ``program_id`` (``mn``/``mx``
    = ``jnp.minimum``/``maximum``) and the census on Python ints."""
    hi = -(-t_kv // block_k)
    n_clean = 0 if has_mask else t_kv // block_k
    if not causal:
        return n_clean, hi
    first_row_sees = qi * block_q + (t_kv - t_q)    # bottom-right aligned
    last_row_sees = first_row_sees + block_q - 1
    hi = mn(hi, mx(last_row_sees + block_k, 0) // block_k)
    full = mn(n_clean, mx(first_row_sees + 1, 0) // block_k)
    return mn(full, hi), hi


def _k_tile_bands(qi, *, window=None, mn=min, mx=max, **geom):
    """``(lo, b, c, hi)`` for q block ``qi`` (fwd and dq): k tiles
    ``[lo, b)`` build the mask (with a window: its trailing edge cuts
    them), ``[b, c)`` run mask-free, ``[c, hi)`` build the mask (the
    diagonal, the kv padding, a key-padding mask), and tiles under ``lo``
    or from ``hi`` on hold no visible key. Without a window ``lo = b = 0``
    and ``(c, hi)`` is ``_k_tile_range``. A window of ``w`` keys lets row
    ``i`` see ``i - w < j <= i`` (bottom-right aligned like the
    diagonal)."""
    n_full, hi = _k_tile_range(qi, mn=mn, mx=mx, **geom)
    if window is None:
        return 0, 0, n_full, hi
    block_q, block_k = geom["block_q"], geom["block_k"]
    first_row_sees = qi * block_q + (geom["t_kv"] - geom["t_q"])
    last_row_sees = first_row_sees + block_q - 1
    # the first tile any row's window reaches, and the first one that
    # every row's window holds whole
    lo = mn(mx(first_row_sees - window + 1, 0) // block_k, hi)
    whole = (mx(last_row_sees - window + 1, 0) + block_k - 1) // block_k
    b = mn(mx(whole, lo), hi)
    return lo, b, mn(mx(n_full, b), hi), hi


def _q_tile_range(ki, *, block_q, block_k, t_q, t_kv, causal,
                  mn=min, mx=max):
    """``(lo, full, hi)`` for k block ``ki`` (dkv): q tiles ``[lo, full)``
    are crossed by the causal diagonal, ``[full, hi)`` see every key of the
    block, tiles under ``lo`` see none of them. Padded keys and keys the
    key-padding mask hides only touch their own rows of dk/dv, which the
    wrapper drops, so neither makes a tile a masked one here."""
    hi = -(-t_q // block_q)
    if not causal:
        return 0, 0, hi
    first_row = ki * block_k - (t_kv - t_q)     # first q row seeing a key
    all_row = first_row + block_k - 1           # first q row seeing all
    lo = mn(hi, mx(first_row, 0) // block_q)
    full = mn(hi, mx(all_row + block_q - 1, 0) // block_q)
    return lo, mx(full, lo), hi


def _q_tile_bands(ki, *, window=None, mn=min, mx=max, **geom):
    """``(lo, b, c, hi)`` for k block ``ki`` (dkv), the transposed plane:
    q tiles ``[lo, b)`` are crossed by the diagonal, ``[b, c)`` see every
    key of the block, ``[c, hi)`` are cut by the window's trailing edge
    (some row's window starts inside the block), tiles under ``lo`` or
    from ``hi`` on see none of its keys. Without a window ``c = hi``."""
    lo, full, hi = _q_tile_range(ki, mn=mn, mx=mx, **geom)
    if window is None:
        return lo, full, hi, hi
    block_q, block_k = geom["block_q"], geom["block_k"]
    # row r sees key j iff j > r + offset - window: the rows under
    # `all_to` see the block's first key (so all of it), the rows under
    # `any_to` its last
    all_to = ki * block_k - (geom["t_kv"] - geom["t_q"]) + window
    any_to = all_to + block_k - 1
    hi = mn(hi, (mx(any_to, 0) + block_q - 1) // block_q)
    lo = mn(lo, hi)
    b = mn(full, hi)
    return lo, b, mn(mx(mx(all_to, 0) // block_q, b), hi), hi


def _tile_census(t_q: int, t_kv: int, tiling: _Tiling, causal: bool,
                 has_mask: bool, window: Optional[int] = None) -> dict:
    """Static census of one (batch, head) of a forward call: compute tiles
    that run mask-free, tiles that build the mask, and grid steps whose
    loops are empty (a causal major window wholly right of the diagonal,
    or wholly behind the window). A window call also counts the tiles of
    the causal half that it never computes (``skipped_tiles``). dq walks
    the same plane at its own tile; dkv the transposed one."""
    bq, bk, major = tiling
    tiles = major // bk
    n_major = -(-max(t_kv, 1) // major)
    out = {"interior": 0, "masked": 0, "skipped_steps": 0}
    if window is not None:
        out["skipped_tiles"] = 0
    for qi in range(-(-max(t_q, 1) // bq)):
        geom = dict(block_q=bq, block_k=bk, t_q=t_q, t_kv=t_kv,
                    causal=causal, has_mask=has_mask)
        lo, b, c, hi = _k_tile_bands(qi, window=window, **geom)
        out["interior"] += c - b
        out["masked"] += (b - lo) + (hi - c)
        out["skipped_steps"] += sum(
            hi <= kj * tiles or lo >= (kj + 1) * tiles
            for kj in range(n_major))
        if window is not None:
            out["skipped_tiles"] += lo
    return out


def _tile_loop(lo, hi, body) -> None:
    """``for j in [lo, hi): body(j)``, rolled; the state lives in refs and
    the bounds come from ``program_id`` arithmetic."""
    def step(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)


def _walk_k_tiles(tile, qi, base, tiles: int, *, block_k, t_kv, causal,
                  has_mask, window=None, **geom) -> None:
    """fwd and dq: ``tile(j, masked)`` over the k tiles of the major window
    that starts at global tile ``base``, the mask-free run first (after the
    band the window's trailing edge cuts, where there is a window). A loop
    that can never run at this signature is not emitted."""
    if window is not None:
        lo, b, c, hi = (jnp.clip(n - base, 0, tiles) for n in _k_tile_bands(
            qi, window=window, block_k=block_k, t_kv=t_kv, causal=causal,
            has_mask=has_mask, mn=jnp.minimum, mx=jnp.maximum, **geom))
        _tile_loop(lo, b, lambda j: tile(j, True))
        if not has_mask:
            _tile_loop(b, c, lambda j: tile(j, False))
        _tile_loop(c, hi, lambda j: tile(j, True))
        return
    n_full, hi = (jnp.clip(n - base, 0, tiles) for n in _k_tile_range(
        qi, block_k=block_k, t_kv=t_kv, causal=causal, has_mask=has_mask,
        mn=jnp.minimum, mx=jnp.maximum, **geom))
    if not has_mask:
        _tile_loop(0, n_full, lambda j: tile(j, False))
    if causal or has_mask or t_kv % block_k:
        _tile_loop(n_full, hi, lambda j: tile(j, True))


def _rows(ref, j, block: int):
    """Tile ``j`` (``block`` rows) of the major window ``ref`` holds."""
    return ref[0, pl.ds(pl.multiple_of(j * block, block), block), :]


def _visibility(q_tile, k_tile, shape, *, block_q, block_k, t_kv, offset,
                causal, mask_row=None, window=None):
    """Keep-mask of one (q, k) score tile: kv padding, causal alignment
    (and the window behind it), and the optional key-padding mask row."""
    k_pos = k_tile * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = k_pos < t_kv
    if causal:
        q_pos = q_tile * block_q + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0)
        ok = ok & (k_pos <= q_pos + offset)
        if window is not None:
            ok = ok & (k_pos > q_pos + (offset - window))
    if mask_row is not None:
        # keep-masks are a binary contract (1.0 = attend); >= 1.0 matches
        # the XLA oracle's additive -1e9*(1-mask) on stray soft values too
        # (anything < 1 is effectively hidden there)
        ok = ok & (mask_row >= 1.0)
    return ok


def _stat_chunks(n: int):
    """Static (start, size) runs the row statistics are re-oriented in:
    128 rows at a time keeps the identity pattern at 16 vregs."""
    step = _LANES if n % _LANES == 0 else n
    return [(lo, step) for lo in range(0, n, step)]


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _store_as_row(row_ref, col) -> None:
    """Write the (n, 1) column ``col`` into the (1, n) ``row_ref``. The
    forward reduces along lanes, so its row statistics come out one per
    sublane; HBM keeps them one float wide, along lanes. Select-and-sum
    against an identity pattern is exact (one term a column, +inf
    included) and needs no transpose."""
    for lo, step in _stat_chunks(col.shape[0]):
        # a single unaligned run is the whole ref: Mosaic refuses a
        # 40-wide slice of a window it padded to 128 lanes
        where = (slice(None), slice(lo, lo + step)) \
            if step != col.shape[0] else Ellipsis
        row_ref[where] = jnp.sum(
            jnp.where(_eye(step), col[lo:lo + step], 0.0), axis=0,
            keepdims=True)


def _store_as_col(col_ref, row_ref) -> None:
    """The inverse, for the dq kernel, whose (q, k) tile wants its row
    statistics one per sublane: the (1, n) ``row_ref`` into lane 0 of the
    (n, LANES) scratch ``col_ref``."""
    n = row_ref.shape[-1]
    for lo, step in _stat_chunks(n):
        row = row_ref[:, lo:lo + step] if step != n else row_ref[...]
        col_ref[lo:lo + step, :1] = jnp.sum(
            jnp.where(_eye(step), row, 0.0), axis=1, keepdims=True)


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    """Operands stay in the input dtype (bf16 operands = full MXU rate);
    the product accumulates f32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(*refs, scale: float, fold_scale: bool, tiling: _Tiling,
                n_major: int, t_q: int, t_kv: int, causal: bool,
                has_mask: bool, want_lse: bool, heads: _Heads,
                window: Optional[int] = None):
    """Grid cell (c, qi, kj): one q block against major window ``kj`` of
    K/V, walked tile by tile up to the causal diagonal (from the window's
    trailing edge on, where there is a window). q (1, block_q, W);
    k/v (1, major_k, W); [mask (1, tiles, 1, block_k)]; o (1, block_q, W);
    lse (P, 1, 1, block_q); scratch acc (P, block_q, W), m/l
    (P, block_q, LANES) carry the online softmax across tiles and major
    windows. ``c`` counts heads (P = 1, W = D) or, in the pair layout,
    pairs of them (P = 2, W = 128: head ``hh``'s lanes of the resident q
    block stand alone on its key head's half, so a contraction over all
    128 lanes is that head's score tile, and ``p @ v`` is its output on
    that half and another head's on the other)."""
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    rest = refs[3 + int(has_mask):]
    o_ref = rest[0]
    lse_ref = rest[1] if want_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    block_q, block_k, major = tiling
    tiles = major // block_k
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    base = kj * tiles
    offset = t_kv - t_q  # bottom-right causal alignment

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    def head(hh: int, kh) -> None:
        q = q_ref[0]
        if kh is not None:
            q = _onto(q, hh, kh)
        if fold_scale:      # a power of two: exact in any float dtype
            q = q * scale

        def tile(j, masked: bool):
            s = _dot(q, _rows(k_ref, j, block_k), _NT)
            if not fold_scale:
                s = s * scale
            m_prev = m_ref[hh, :, :1]
            if masked:
                ok = _visibility(
                    qi, base + j, s.shape, block_q=block_q, block_k=block_k,
                    t_kv=t_kv, offset=offset, causal=causal, window=window,
                    mask_row=mask_ref[0, j] if has_mask else None)
                s = jnp.where(ok, s, -jnp.inf)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                # a row with no visible key yet keeps m = -inf;
                # exp(-inf - 0) is the 0 its p and its correction need
                m_use = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            else:
                # every score is a visible one: m_new is finite, nothing
                # to guard
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                m_use = m_new
            p = jnp.exp(s - m_use)
            corr = jnp.exp(m_prev - m_use)
            l_ref[hh, :, :1] = l_ref[hh, :, :1] * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[hh] = acc_ref[hh] * corr + _dot(
                p.astype(v_ref.dtype), _rows(v_ref, j, block_k), _NN)
            m_ref[hh, :, :1] = m_new

        _walk_k_tiles(tile, qi, base, tiles, block_q=block_q,
                      block_k=block_k, t_q=t_q, t_kv=t_kv, causal=causal,
                      has_mask=has_mask, window=window)

    halves = [heads.kv_half(hh, pl.program_id(0))
              for hh in range(heads.per_cell)]
    for hh, kh in enumerate(halves):
        head(hh, kh)

    @pl.when(kj == n_major - 1)
    def _finish():
        out = None
        for hh, kh in enumerate(halves):
            l = l_ref[hh, :, :1]
            m = m_ref[hh, :, :1]
            o = acc_ref[hh] / jnp.where(l == 0.0, 1.0, l)
            if kh is not None:      # its own half, the other's lanes zero
                o = _onto(o, kh, hh)
            out = o if out is None else out + o
            if want_lse:
                # rows with no visible key: +inf sentinel makes every
                # backward probability exp(s - inf) = 0, matching the zero
                # forward output
                _store_as_row(lse_ref.at[hh, 0], jnp.where(
                    l == 0.0, jnp.inf,
                    m + jnp.log(jnp.where(l == 0.0, 1.0, l))))
        o_ref[0] = out.astype(o_ref.dtype)


def _q_cell_specs(tiling: _Tiling, n_major: int, heads: _Heads,
                  window: Optional[int] = None, **geom):
    """BlockSpecs of a fwd/dq cell (c, qi, kj): the q block, the K/V major
    window, the mask rows of that window and a q tile's row statistics.
    Window ``kj`` is clamped to the ones the q block needs, so a causal
    step right of the diagonal (or one behind the attention window)
    repeats a block index and costs no DMA. A head (a pair of them) is a
    lane block of its batch row's ``(T, H*D)`` plane, or a row of its own
    in the split layout (``_Heads.row_and_block``). With grouped heads
    (``group`` query cells to a key/value cell) K/V are indexed by
    ``cell // group``: nothing is repeated in HBM, and the query heads of
    a group find their K/V block resident."""
    block_q, block_k, major = tiling
    tiles = major // block_k

    def major_window(qi, kj):
        if n_major == 1:
            return 0
        if window is None:
            _, hi = _k_tile_range(qi, block_q=block_q, block_k=block_k,
                                  mn=jnp.minimum, mx=jnp.maximum, **geom)
            return jnp.minimum(kj, jnp.maximum(hi - 1, 0) // tiles)
        lo, _, _, hi = _k_tile_bands(
            qi, window=window, block_q=block_q, block_k=block_k,
            mn=jnp.minimum, mx=jnp.maximum, **geom)
        return jnp.clip(kj, lo // tiles, jnp.maximum(hi - 1, 0) // tiles)

    def q_at(c, qi, kj):
        row, blk = heads.row_and_block(c, heads.cells)
        return row, qi, blk

    def kv_at(c, qi, kj):
        # c // group is the key/value cell over the whole batch as well as
        # inside a batch row (cells = group x kv_cells)
        row, blk = heads.row_and_block(c // heads.group, heads.kv_cells)
        return row, major_window(qi, kj), blk

    return (pl.BlockSpec((1, block_q, heads.width), q_at),
            pl.BlockSpec((1, major, heads.width), kv_at),
            pl.BlockSpec((1, tiles, 1, block_k), lambda c, qi, kj: (
                c // heads.cells, major_window(qi, kj), 0, 0)),
            pl.BlockSpec((heads.per_cell, 1, 1, block_q),
                         lambda c, qi, kj: (c, qi, 0, 0)))


def _rows_padded(x, mult: int, heads: _Heads):
    """(B, T, H, D) as the kernels take it, T rounded up to ``mult``:
    ``(B, T, H*D)``, a free reshape, where they find a head in place;
    ``(B*H, T, D)`` through a transpose in the split layout."""
    b, t, h, d = x.shape
    x = (x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
         if heads.layout == "split" else x.reshape(b, t, h * d))
    return pad_to_multiple(x, 1, mult)


def _heads_back(x, heads: _Heads, b: int, h: int, t: int, d: int):
    """A kernel's output as (B, T, H, D), the padding cut off."""
    if heads.layout == "split":
        return x[:, :t, :].reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return x[:, :t, :].reshape(b, t, h, d)


def _mask_rows(mask, tiling: _Tiling):
    """The (B, Tk) keep-mask as one (1, block_k) row per k tile: a tile's
    row is a leading-dim index away, and broadcasts over the score tile's
    sublanes."""
    mr = pad_to_multiple(mask.astype(jnp.float32), 1, tiling.major)
    return mr.reshape(mr.shape[0], -1, 1, tiling.block_k)


def _stat_rows(x, t_pad: int, block_q: int):
    """A (B*H, 1, T) row statistic as (B*H, q tiles, 1, block_q): a q
    tile's statistics are a leading-dim index away, q along lanes."""
    return pad_to_multiple(x, 2, t_pad).reshape(x.shape[0], -1, 1, block_q)


def _compiler_params(kernel: str, tiling: _Tiling, d: int, itemsize: int,
                     has_mask: bool, heads_per_cell: int = 1):
    """Mosaic's scoped-VMEM default holds every schedule the selector
    makes at the default budget (it fits them into half of it); a call
    whose estimate is over that half (a raised ``zoo.pallas.
    vmem_budget_mb``, or floor tiles that already outgrow it) asks for
    its own limit."""
    est = _kernel_vmem_bytes(tiling.block_q, tiling.block_k, d, itemsize,
                             has_mask, major=tiling.major, kernel=kernel,
                             heads=heads_per_cell)
    if 2 * est <= _VMEM_BYTES_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=2 * est)


# jitted, inline: a model's layers make the same call, and a `jit` of the
# same function at the same signature is traced once (a bare `pallas_call`
# traces its kernel again for every layer: 0.14-0.34 s a call on the chip's
# host, 36 calls a step); `inline` puts each call's equations into the
# caller, so the program still holds one Mosaic call per layer under the
# names it had
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8), inline=True)
def _flash_fwd(q, k, v, mask, causal: bool, sched: _Schedule,
               interpret: bool, want_lse: bool,
               window: Optional[int] = None):
    b, t_q, h, d = q.shape
    t_kv = k.shape[1]
    heads = _head_layout(h, k.shape[2], d)
    scale = 1.0 / float(d) ** 0.5
    block_q, block_k, major = tiling = sched.fwd
    qr = _rows_padded(q, block_q, heads)
    kr, vr = (_rows_padded(a, major, heads) for a in (k, v))
    n_q = qr.shape[1] // block_q
    n_major = kr.shape[1] // major
    has_mask = mask is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, fold_scale=_is_pow2(scale), tiling=tiling,
        n_major=n_major, t_q=t_q, t_kv=t_kv, causal=causal,
        has_mask=has_mask, want_lse=want_lse, heads=heads, window=window)
    qspec, kspec, mspec, rowspec = _q_cell_specs(
        tiling, n_major, heads, window=window, t_q=t_q, t_kv=t_kv,
        causal=causal, has_mask=has_mask)
    in_specs = [qspec, kspec, kspec]
    operands = [qr, kr, vr]
    if has_mask:
        in_specs.append(mspec)
        operands.append(_mask_rows(mask, tiling))
    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct(qr.shape, q.dtype)]
    if want_lse:
        # inference/primal calls skip the lse output entirely — pallas
        # outputs are opaque to XLA DCE, so an unconditional write would
        # cost real HBM traffic on every no-grad forward
        out_specs.append(rowspec)
        out_shape.append(jax.ShapeDtypeStruct(
            (b * h, n_q, 1, block_q), jnp.float32))
    per = heads.per_cell
    res = pl.pallas_call(
        kernel,
        grid=(b * heads.cells, n_q, n_major),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((per, block_q, heads.width), jnp.float32),  # acc
            pltpu.VMEM((per, block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((per, block_q, _LANES), jnp.float32),  # and denom
        ],
        compiler_params=_compiler_params("fwd", tiling, d, q.dtype.itemsize,
                                         has_mask, per),
        interpret=interpret,
        name="zoo_flash_fwd" + _name_suffix(window),
    )(*operands)
    o = _heads_back(res[0], heads, b, h, t_q, d)
    if not want_lse:
        return o
    # the residual is one float a row, whatever the tile: (B*H, 1, T)
    return o, res[1].reshape(b * h, 1, -1)[:, :, :t_q]


def _is_pow2(x: float) -> bool:
    return math.frexp(x)[0] == 0.5


def _name_suffix(window: Optional[int]) -> str:
    """Window calls carry ``_win`` behind the kernel's name, so that a
    trace tells a window layer's calls from a full layer's."""
    return "" if window is None else "_win"


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale: float, fold_scale: bool, tiling: _Tiling,
                   n_major: int, t_q: int, t_kv: int, causal: bool,
                   has_mask: bool, heads: _Heads,
                   window: Optional[int] = None):
    """Grid (c, qi, kj), the forward's schedule: dq of one q block
    accumulates over the k tiles of each major window. lse arrives
    (P, 1, 1, block_q), one float a row along lanes, and is stood up into
    sublanes once a q block for the (q, k) tile. ``delta = rowsum(dO * O)``
    is taken HERE, once a q block, from the dO block the cell holds and the
    O block beside it: it comes out one float a sublane, as the tile wants
    it, and goes out (P, 1, 1, block_q) for the dkv kernel (in XLA the
    same sum over a head's lanes of a ``(B, T, H*D)`` array costs a change
    of layout of the whole product). In the pair layout head ``hh``'s
    lanes of q and of dO stand alone on its key head's half, as in the
    forward, and ``ds @ k`` is its dq on that half."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, mask_ref, dq_ref,
         dl_ref, acc_ref, lse_col, dl_col) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dl_ref,
         acc_ref, lse_col, dl_col) = refs
        mask_ref = None
    block_q, block_k, major = tiling
    tiles = major // block_k
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    base = kj * tiles
    offset = t_kv - t_q
    halves = [heads.kv_half(hh, pl.program_id(0))
              for hh in range(heads.per_cell)]

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for hh in range(heads.per_cell):
            _store_as_col(lse_col.at[hh], lse_ref.at[hh, 0])
            delta = jnp.sum(prod if heads.per_cell == 1
                            else _onto(prod, hh, hh), axis=-1, keepdims=True)
            dl_col[hh, :, :1] = delta
            _store_as_row(dl_ref.at[hh, 0], delta)

    def head(hh: int, kh) -> None:
        q, do = q_ref[0], do_ref[0]
        if kh is not None:
            q, do = _onto(q, hh, kh), _onto(do, hh, kh)
        if fold_scale:
            q = q * scale

        def tile(j, masked: bool):
            k = _rows(k_ref, j, block_k)
            s = _dot(q, k, _NT)
            if not fold_scale:
                s = s * scale
            p = jnp.exp(s - lse_col[hh, :, :1])
            if masked:
                p = jnp.where(_visibility(
                    qi, base + j, s.shape, block_q=block_q, block_k=block_k,
                    t_kv=t_kv, offset=offset, causal=causal, window=window,
                    mask_row=mask_ref[0, j] if has_mask else None), p, 0.0)
            dp = _dot(do, _rows(v_ref, j, block_k), _NT)
            ds = p * (dp - dl_col[hh, :, :1])
            acc_ref[hh] += _dot(ds.astype(k.dtype), k, _NN)

        _walk_k_tiles(tile, qi, base, tiles, block_q=block_q,
                      block_k=block_k, t_q=t_q, t_kv=t_kv, causal=causal,
                      has_mask=has_mask, window=window)

    for hh, kh in enumerate(halves):
        head(hh, kh)

    @pl.when(kj == n_major - 1)
    def _finish():
        dq = None
        for hh, kh in enumerate(halves):
            part = acc_ref[hh] * scale
            if kh is not None:
                part = _onto(part, kh, hh)
            dq = part if dq is None else dq + part
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, scale: float,
                    fold_scale: bool, tiling: _Tiling, n_major: int,
                    t_q: int, t_kv: int, causal: bool, heads: _Heads,
                    window: Optional[int] = None):
    """Grid (c, ki, qj): dk/dv of one k block accumulate over the q tiles
    of each major window, from the causal diagonal on (up to the window's
    trailing edge, where there is a window). ``c`` counts key/value cells
    and ``qj`` runs over the major windows of each of the ``group`` query
    cells that share it, one after the other, into the same accumulators.
    The tile is formed transposed, ``s^T = k q^T`` (block_k, block_q): the
    row statistics (1, block_q) broadcast over its sublanes as they are
    stored, and ``dV += p^T dO``, ``dK += ds^T q`` are plain products.

    Pair layout: the q/dO window holds two query heads; for head ``hh`` of
    them the resident k and v blocks keep their key head's lanes alone,
    moved onto ``hh``'s half, so the contractions over 128 lanes are that
    head's, and ``p^T dO`` / ``ds^T q`` carry its share of dv / dk on
    ``hh``'s half of accumulator ``hh``. Without grouped heads half ``hh``
    IS key head ``hh`` and the two halves are the block. With them all the
    query heads of a step belong to ONE key head, the first half of the
    steps to the block's first: at the end of either half of the steps the
    two accumulators' halves are summed onto that key head's lanes."""
    block_q, block_k, major = tiling
    tiles = major // block_q
    group, per = heads.group, heads.per_cell
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    base = (qj if group == 1 else qj % n_major) * tiles
    offset = t_kv - t_q
    # a grouped pair: the key head (half of the k block) this step's two
    # query heads attend
    step_half = (2 * (qj // n_major)) // group if per == 2 and group > 1 \
        else None

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def head(hh: int) -> None:
        k, v = k_ref[0], v_ref[0]
        if per == 2:
            src = hh if step_half is None else step_half
            k, v = _onto(k, src, hh), _onto(v, src, hh)
        if fold_scale:
            k = k * scale

        def tile(i, masked: bool):
            q = _rows(q_ref, i, block_q)
            do = _rows(do_ref, i, block_q)
            st = _dot(k, q, _NT)
            if not fold_scale:
                st = st * scale
            pt = jnp.exp(st - lse_ref[hh, i])
            if masked:      # the causal diagonal, on a (k, q) tile
                q_pos = (base + i) * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1)
                k_pos = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 0)
                ok = k_pos <= q_pos + offset
                if window is not None:
                    ok = ok & (k_pos > q_pos + (offset - window))
                pt = jnp.where(ok, pt, 0.0)
            dv_acc[hh] += _dot(pt.astype(do.dtype), do, _NN)
            dst = pt * (_dot(v, do, _NT) - dl_ref[hh, i])
            dk_acc[hh] += _dot(dst.astype(q.dtype), q, _NN)

        geom = dict(block_q=block_q, block_k=block_k, t_q=t_q, t_kv=t_kv,
                    causal=causal, mn=jnp.minimum, mx=jnp.maximum)
        if window is None:
            lo, full, hi = (jnp.clip(n - base, 0, tiles)
                            for n in _q_tile_range(ki, **geom))
            if causal:
                _tile_loop(lo, full, lambda i: tile(i, True))
            _tile_loop(full, hi, lambda i: tile(i, False))
        else:
            lo, b, c, hi = (jnp.clip(n - base, 0, tiles) for n in
                            _q_tile_bands(ki, window=window, **geom))
            _tile_loop(lo, b, lambda i: tile(i, True))
            _tile_loop(b, c, lambda i: tile(i, False))
            _tile_loop(c, hi, lambda i: tile(i, True))

    for hh in range(per):
        head(hh)

    def own_halves(acc):
        """Accumulator ``hh``'s half ``hh``, side by side."""
        return _onto(acc[0], 0, 0) + _onto(acc[1], 1, 1)

    last = group * n_major - 1
    if per == 1:
        @pl.when(qj == last)
        def _finish():
            dk_ref[0] = (dk_acc[0] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[0].astype(dv_ref.dtype)
    elif step_half is None:
        @pl.when(qj == last)
        def _finish_pair():
            dk_ref[0] = (own_halves(dk_acc) * scale).astype(dk_ref.dtype)
            dv_ref[0] = own_halves(dv_acc).astype(dv_ref.dtype)
    else:
        def summed(acc):
            """Both query heads' shares, on both halves."""
            both = own_halves(acc)
            return both + pltpu.roll(both, _HALF, 1)

        @pl.when(qj == last // 2)
        def _first_key_head():
            # whole blocks: the second key head's lanes are overwritten
            # at the last step
            dk_ref[0] = (summed(dk_acc) * scale).astype(dk_ref.dtype)
            dv_ref[0] = summed(dv_acc).astype(dv_ref.dtype)
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        @pl.when(qj == last)
        def _second_key_head():
            upper = jax.lax.broadcasted_iota(
                jnp.int32, dk_ref.shape[1:], 1) >= _HALF
            dk_ref[0] = jnp.where(
                upper, (summed(dk_acc) * scale).astype(dk_ref.dtype),
                dk_ref[0])
            dv_ref[0] = jnp.where(
                upper, summed(dv_acc).astype(dv_ref.dtype), dv_ref[0])


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10), inline=True)
def _flash_bwd(q, k, v, mask, out, lse, g, causal, sched: _Schedule,
               interpret, window: Optional[int] = None):
    b, t_q, h, d = q.shape
    t_kv, h_kv = k.shape[1], k.shape[2]
    heads = _head_layout(h, h_kv, d)
    group, per = heads.group, heads.per_cell
    scale = 1.0 / float(d) ** 0.5
    fold = _is_pow2(scale)
    has_mask = mask is not None
    itemsize = q.dtype.itemsize
    geom = dict(t_q=t_q, t_kv=t_kv, causal=causal)

    block_q, block_k, major = tiling = sched.dq
    qr, gr, outr = (_rows_padded(a, block_q, heads) for a in (q, g, out))
    kr, vr = (_rows_padded(a, major, heads) for a in (k, v))
    n_major = kr.shape[1] // major
    qspec, kspec, mspec, rowspec = _q_cell_specs(
        tiling, n_major, heads, window=window, has_mask=has_mask, **geom)
    n_q = qr.shape[1] // block_q
    # delta_i = sum_d dO_id * O_id, like lse one float a row, comes out of
    # the dq kernel beside dq
    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, fold_scale=fold,
                          tiling=tiling, n_major=n_major, has_mask=has_mask,
                          heads=heads, window=window, **geom),
        grid=(b * heads.cells, n_q, n_major),
        in_specs=[qspec, kspec, kspec, qspec, qspec, rowspec]
                 + ([mspec] if has_mask else []),
        out_specs=[qspec, rowspec],
        out_shape=[jax.ShapeDtypeStruct(qr.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * h, n_q, 1, block_q),
                                        jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((per, block_q, heads.width), jnp.float32),
            pltpu.VMEM((per, block_q, _LANES), jnp.float32),  # lse
            pltpu.VMEM((per, block_q, _LANES), jnp.float32)],  # delta
        compiler_params=_compiler_params("dq", tiling, d, itemsize,
                                         has_mask, per),
        interpret=interpret,
        name="zoo_flash_bwd_dq" + _name_suffix(window),
    )(qr, kr, vr, gr, outr, _stat_rows(lse, qr.shape[1], block_q),
      *([_mask_rows(mask, tiling)] if has_mask else []))
    delta = delta.reshape(b * h, 1, -1)[:, :, :t_q]

    # dk/dv grid: (c, ki, qj) — c counts key/value cells, the q side is
    # the resident major window, from the first one the k block's causal
    # diagonal reaches
    block_q, block_k, major = tiling = sched.dkv
    qr, gr = (_rows_padded(a, major, heads) for a in (q, g))
    kr, vr = (_rows_padded(a, block_k, heads) for a in (k, v))
    tiles = major // block_q
    n_major = qr.shape[1] // major

    def window2(ki, qj):
        if n_major == 1:
            return 0
        if group > 1:
            qj = qj % n_major
        lo, _, _, hi = _q_tile_bands(
            ki, window=window, block_q=block_q, block_k=block_k,
            mn=jnp.minimum, mx=jnp.maximum, **geom)
        return jnp.clip(qj, lo // tiles, jnp.maximum(hi - 1, 0) // tiles)

    def q_cell(c, qj):
        # step qj belongs to query cell qj // n_major of c's group
        return c if group == 1 else c * group + qj // n_major

    def q_at(c, ki, qj):
        row, blk = heads.row_and_block(q_cell(c, qj), heads.cells)
        return row, window2(ki, qj), blk

    def k_at(c, ki, qj):
        row, blk = heads.row_and_block(c, heads.kv_cells)
        return row, ki, blk

    qspec2 = pl.BlockSpec((1, major, heads.width), q_at)
    kspec2 = pl.BlockSpec((1, block_k, heads.width), k_at)
    rowspec2 = pl.BlockSpec((per, tiles, 1, block_q), lambda c, ki, qj: (
        q_cell(c, qj), window2(ki, qj), 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, fold_scale=fold,
                          tiling=tiling, n_major=n_major, heads=heads,
                          window=window, **geom),
        grid=(b * heads.kv_cells, kr.shape[1] // block_k, group * n_major),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(kr.shape, k.dtype),
                   jax.ShapeDtypeStruct(vr.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((per, block_k, heads.width), jnp.float32),
                        pltpu.VMEM((per, block_k, heads.width), jnp.float32)],
        compiler_params=_compiler_params("dkv", tiling, d, itemsize, False,
                                         per),
        interpret=interpret,
        name="zoo_flash_bwd_dkv" + _name_suffix(window),
    )(qr, kr, vr, gr, _stat_rows(lse, qr.shape[1], block_q),
      _stat_rows(delta, qr.shape[1], block_q))

    dq = _heads_back(dq, heads, b, h, t_q, d)
    dk = _heads_back(dk, heads, b, h_kv, t_kv, d)
    dv = _heads_back(dv, heads, b, h_kv, t_kv, d)
    dmask = None
    if has_mask:
        # a hidden key takes part in no row's softmax, so it only ever
        # touches its own rows of dk/dv: the dkv kernel leaves them
        # unmasked and they are dropped here (a select, not a product —
        # exp(s - lse) of a hidden key is unbounded)
        keep = (mask >= 1.0)[:, :, None, None]
        dk = jnp.where(keep, dk, jnp.zeros_like(dk))
        dv = jnp.where(keep, dv, jnp.zeros_like(dv))
        dmask = jnp.zeros_like(mask, dtype=jnp.float32)
    return dq, dk, dv, dmask


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, causal, sched, interpret, window=None):
    return _flash_fwd(q, k, v, mask, causal, sched, interpret, False, window)


def _vjp_fwd(q, k, v, mask, causal, sched, interpret, window):
    out, lse = _flash_fwd(q, k, v, mask, causal, sched, interpret, True,
                          window)
    # named HERE, and the named values handed on both as the output and as
    # residuals: a name put on the layer's output from outside marks
    # another variable than the residual, and a checkpoint that keeps it
    # still runs the kernel again for the backward
    out, lse = (_saved(x, name) for x, name in zip((out, lse), FLASH_SAVED))
    return out, (q, k, v, mask, out, lse)


def _vjp_bwd(causal, sched, interpret, window, res, g):
    q, k, v, mask, out, lse = res
    return _flash_bwd(q, k, v, mask, out, lse, g, causal, sched, interpret,
                      window)


_flash.defvjp(_vjp_fwd, _vjp_bwd)


def _flash_per_data_shard(q, k, v, mask, causal, sched, interpret,
                          window=None):
    """Run the kernel once per ``data`` shard. A Mosaic kernel refuses to
    lower inside a jit that spans several devices ("Mosaic kernels cannot
    be automatically partitioned" — found on a four-chip v5e host, PR 21:
    the default data-parallel mesh could not train through this kernel at
    all), and batch rows are independent, so on a mesh with a data axis
    the call is wrapped in a ``shard_map`` over the batch dim. A plain
    call where there is nothing to split, where the batch does not
    divide, and inside a ``shard_map`` body (ring and pipeline stages),
    whose operands are per-shard already."""
    from jax.sharding import PartitionSpec as P

    from ...parallel import mesh as mesh_lib
    mesh = mesh_lib.global_mesh()
    dp = mesh.shape[mesh_lib.DATA_AXIS]
    if dp == 1 or q.shape[0] % dp or mesh_lib.in_manual_region():
        return _flash(q, k, v, mask, causal, sched, interpret, window)
    args = (q, k, v) if mask is None else (q, k, v, mask)

    def local(q, k, v, m=None):
        return _flash(q, k, v, m, causal, sched, interpret, window)

    batch = P(mesh_lib.DATA_AXIS)
    return jax.shard_map(local, mesh=mesh, in_specs=(batch,) * len(args),
                         out_specs=batch, check_vma=False)(*args)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Blockwise-softmax attention: q (B, Tq, Hq, D), k/v (B, Tk, Hkv, D)
    with ``Hq % Hkv == 0`` → (B, Tq, Hq, D): the layout a projection's
    output reshapes to for nothing. The kernels find a head in place where
    its width allows (D a multiple of 128: a lane block of the ``(T, H*D)``
    plane; D = 64 with even head counts: two heads a block) and the
    wrapper splits the heads out where it does not (the module docstring's
    "Heads in place"); the choice is made from the head width and counts
    alone. Query head ``h`` attends key/value head ``h // (Hq / Hkv)``;
    K/V are indexed so by the block specs (nothing is repeated in HBM) and
    dk/dv accumulate over the query heads of a group inside the dkv kernel.

    ``window`` (causal calls): a query sees the ``window`` latest keys up
    to and with its own position, ``i - window < j <= i``. The k-tile loop
    of fwd and dq and the q-tile loop of dkv are then bounded on both
    sides, and the mask is built on the tiles the diagonal or the window's
    trailing edge cuts; a window no shorter than the keys is no window.

    ``mask``: optional per-batch key-padding keep-mask, (B, Tk), a BINARY
    contract: values >= 1.0 attend, anything below is hidden — matching the
    XLA oracle's additive ``-1e9*(1-mask)`` on stray soft values (the BERT
    ``attention_mask``; full (B, H, Tq, Tk) masks stay on the XLA op).
    Numerically equivalent to
    ``ops.attention.dot_product_attention`` (minus dropout — that path
    stays on the XLA op). Forward and backward are both Pallas kernels with
    O(block²) memory; gradients flow to q/k/v (the mask gets zeros).
    ``interpret`` defaults to auto: compiled on TPU, interpreter elsewhere
    (tests).

    ``block_q``/``block_k`` default to auto selection
    (``select_attention_blocks``): the swept v5e default
    ``_PREFERRED_BLOCKS`` (its comment holds the chip's readings), shrunk
    when the abstract signature (T, D, dtype, mask) would outgrow the VMEM
    budget. The pair is dq's and dkv's compute tile; the forward walks
    ``_FWD_K_TILES`` k tiles as one; each kernel keeps as much of the
    streamed side resident as the budget holds (the module docstring has
    the schedule). ``zoo.pallas.block_sweep`` refines the pair with a
    one-shot on-device sweep, cached per signature; the choice and its
    static tile census are surfaced as the ``zoo_pallas_block_choice`` and
    ``zoo_pallas_flash_tiles`` gauges. Explicit ints pin the pair (tests,
    reproductions)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mask is not None:
        if isinstance(mask, bool):
            raise TypeError("flash_attention's 4th argument is now the "
                            "key-padding mask; pass causal=... by keyword")
        if mask.ndim != 2:
            raise ValueError(f"flash_attention mask must be (B, Tk); got "
                             f"shape {mask.shape} — reduce broadcast masks "
                             f"at the layer level")
        mask = jax.lax.stop_gradient(mask.astype(jnp.float32))
    has_mask = mask is not None
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"flash_attention: {q.shape[2]} query heads do not "
                         f"divide over {k.shape[2]} key / {v.shape[2]} "
                         f"value heads")
    group = q.shape[2] // k.shape[2]
    if window is not None:
        if not causal or window < 1:
            raise ValueError("flash_attention: window needs causal=True "
                             "and window >= 1")
        if window >= k.shape[1]:
            window = None
    if block_q is None and block_k is None:
        sched = _auto_blocks(q.shape, k.shape[1], q.dtype, causal, has_mask,
                             interpret, window, group)
    else:
        if block_q is None or block_k is None:
            auto = _auto_blocks(q.shape, k.shape[1], q.dtype, causal,
                                has_mask, interpret, window, group)
            block_q = block_q if block_q is not None else auto.dq.block_q
            block_k = block_k if block_k is not None else auto.dq.block_k
        sched = _resolve_schedule(
            q.shape[1], k.shape[1], q.shape[3], q.dtype, has_mask, block_q,
            block_k, heads_per_cell=_head_layout(
                q.shape[2], k.shape[2], q.shape[3]).per_cell)
    return _flash_per_data_shard(q, k, v, mask, causal, sched, interpret,
                                 window)
