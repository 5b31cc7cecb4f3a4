"""Shared helpers for the pallas kernel package: the hardware tile
constants, alignment/padding utilities, and the **parameterized VMEM
footprint estimator** every block selector prices kernels with.

The estimator (:func:`kernel_vmem_bytes` + the per-kernel wrappers
:func:`attention_vmem_bytes` / :func:`ce_vmem_bytes`) is the single
source of truth for "does this block configuration fit VMEM": the
flash-attention autotuner (``flash_attention.select_attention_blocks`` /
``_sweep_candidates``), the fused-CE forward's budget clamp
(``cross_entropy.fused_ce_forward``) and zoolint's static ZL024 check
(``analysis/device.py``) all call the same functions, so a kernel edit
cannot silently change the runtime budget math without the lint-time
check moving with it (``tests/test_pallas.py`` property-tests the
agreement over the autotuner's full candidate set).

IMPORT CONTRACT: this module must stay importable WITHOUT jax — zoolint
loads it standalone (``importlib`` straight off the file, no package
``__init__`` chain) to price pallas_call sites at lint time, and the
linter is jax-free by design. jax imports live inside the functions
that need them (:func:`pad_to_multiple`); everything else is pure-int
arithmetic.
"""

from __future__ import annotations

import logging
from typing import Callable, Iterable, Optional, Sequence, Tuple

log = logging.getLogger("analytics_zoo_tpu.pallas")

LANES = 128     # lane width (TPU min tile last dim)
SUBLANES = 8    # sublane width (TPU min tile second-to-last dim)

#: the VMEM one kernel may count on: Mosaic's default scoped-VMEM limit,
#: 16 MiB — checked on a TPU v5e (``device_kind`` "TPU v5 lite", the only
#: chip this repo has run on), whose physical VMEM is 128 MiB per core. A
#: kernel that needs more asks for it per call
#: (``pltpu.CompilerParams(vmem_limit_bytes=...)``); other chips set
#: ``zoo.pallas.vmem_budget_mb``
VMEM_BYTES_DEFAULT = 16 * 1024 * 1024
#: fraction of VMEM the block selectors hand a kernel — the rest stays
#: with the compiler (spills, the backward's second operand window,
#: semaphores)
VMEM_USABLE_FRACTION = 0.5


def round_up(n: int, mult: int) -> int:
    """``n`` rounded up to the next multiple of ``mult``."""
    return ((n + mult - 1) // mult) * mult


def pad_to_multiple(x, axis: int, mult: int):
    """Zero-pad ``axis`` up to the next multiple of ``mult`` (no-op when
    already aligned)."""
    import jax.numpy as jnp  # lazy: keep this module importable sans jax
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, rem)
    return jnp.pad(x, cfg)


def vmem_budget_bytes() -> int:
    """The live per-core VMEM budget: ``zoo.pallas.vmem_budget_mb`` when
    the zoo context sets it, else the 16 MiB default. (zoolint, which
    loads this file without the package, reads ``VMEM_BYTES_DEFAULT``
    and never calls this.)"""
    from ...common.context import get_zoo_context
    mb = float(get_zoo_context().get("zoo.pallas.vmem_budget_mb", 0) or 0)
    return int(mb * 1024 * 1024) if mb > 0 else VMEM_BYTES_DEFAULT


def vmem_usable_bytes(budget_bytes: Optional[int] = None) -> int:
    """The slice of the budget a kernel may claim for its windows."""
    budget = budget_bytes if budget_bytes is not None else vmem_budget_bytes()
    return int(budget * VMEM_USABLE_FRACTION)


def sweep_fastest(what: str, candidates: Iterable[Tuple[int, int]],
                  timer: Callable[[int, int], float]) -> Tuple[int, int]:
    """The candidate block pair ``timer`` reports fastest — the one-shot
    on-device sweep shared by the flash and CE-backward autotuners. A
    candidate the compiler refuses loses the sweep and says so (WARNING
    with the compiler's message); if every candidate is refused this
    raises rather than hand back a block nothing could run."""
    best, best_t, refused = None, float("inf"), None
    for cand in candidates:
        try:
            t = timer(*cand)
        except Exception as e:  # zoolint: disable=ZL007 logged and chained
            log.warning("%s block sweep: candidate %dx%d refused: %s",
                        what, cand[0], cand[1], e)
            refused = e
            continue
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(f"{what} block sweep: every candidate was "
                           f"refused") from refused
    return best


_ShapeBytes = Tuple[Sequence[int], int]     # ((dims...), itemsize)


def _tile_widened(shape: Sequence[int]) -> int:
    """Element count of ``shape`` with the trailing dim widened to the
    lane tile floor and the second-to-last to the sublane floor — how the
    hardware actually lays a VMEM window out."""
    dims = [max(int(d), 1) for d in shape]
    if not dims:
        return 1
    dims[-1] = round_up(dims[-1], LANES)
    if len(dims) >= 2:
        dims[-2] = round_up(dims[-2], SUBLANES)
    total = 1
    for d in dims:
        total *= d
    return total


def kernel_vmem_bytes(operands: Iterable[_ShapeBytes] = (),
                      outputs: Iterable[_ShapeBytes] = (),
                      scratch: Iterable[_ShapeBytes] = (),
                      compute: Iterable[_ShapeBytes] = (),
                      copies: int = 2) -> int:
    """Parameterized per-grid-cell VMEM footprint: operand and output
    windows are double-buffered (``copies``, the pallas pipeline's
    prefetch depth), scratch and transient compute tiles are single.
    Every shape is widened to the hardware tile floors. Entries are
    ``(shape, itemsize)`` pairs."""
    total = 0
    for shape, itemsize in operands:
        total += copies * _tile_widened(shape) * itemsize
    for shape, itemsize in outputs:
        total += copies * _tile_widened(shape) * itemsize
    for shape, itemsize in scratch:
        total += _tile_widened(shape) * itemsize
    for shape, itemsize in compute:
        total += _tile_widened(shape) * itemsize
    return total


#: the flash forward walks this many of the backward's k tiles as one: its
#: per-tile work beside the products (two lane reductions a row and the
#: rescale of the carried accumulator) grows with block_q alone, so a wider
#: k tile amortises it; the backward kernels carry nothing from tile to tile
ATTENTION_FWD_K_TILES = 2


def attention_budget_scale(d: int) -> int:
    """How much of the VMEM budget a flash schedule of head width ``d`` is
    fitted into, in budgets: 1 up to a lane tile (D = 64, 128: the choices
    swept in PR 27 stand), 2 from D = 256 on. A kernel's windows grow with
    the head width and so does the work each tile does on them, so a wide
    head that is held to a narrow head's budget falls to a tile that
    starves the matrix unit: at D = 256, T = 8192 the (256, 512) tile with
    half the sequence resident reads 89.9 ms a forward + dq + dkv call
    (80 heads, bf16, causal; 69.9 % of roofline), (512, 512) with the whole
    sequence resident 77.2 ms (81.3 %; chip run, PR 33,
    ``scripts/flash-sweep``). The calls ask Mosaic for their own limit
    (``flash_attention._compiler_params``), well inside a v5e core's
    128 MiB. Capped at 2: nothing wider has run."""
    return 2 if d > LANES else 1


def attention_vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int,
                         has_mask: bool = False,
                         major: Optional[int] = None,
                         kernel: Optional[str] = None,
                         heads: int = 1) -> int:
    """Estimated per-grid-cell VMEM of one flash-attention kernel
    (``kernel`` = ``"fwd"``, ``"dq"`` or ``"dkv"``) at the compute tile
    ``(block_q, block_k)``. Left out: the largest of the three at the
    tiles the pair ``(block_q, block_k)`` gives them (the forward's k tile
    is ``ATTENTION_FWD_K_TILES`` of them): what a pair has to fit.

    A kernel holds one block of its own side, the streamed side as a
    resident *major window* of ``major`` rows (K/V for fwd and dq, q/dO
    for dkv; a whole number of tiles, the tile itself when left out), the
    row statistics one float wide ((1, rows) along lanes, a sublane tile
    each in VMEM), its f32 accumulators, and the f32 score-sized tiles its
    loop body keeps live (s and p forward; p, dP and dS backward).
    ``block_k`` prices at the lane floor even as a sublane-position window
    dim because the score tile needs it lane-aligned anyway. ``heads``: the
    heads a grid cell runs, 2 in the pair layout (D = 64, two heads a
    128-lane block): the windows are the 128-wide ones a lone D = 64 head
    is priced at already, the accumulators and stood-up statistics are one
    set a head, and so are dkv's row statistics."""
    if kernel is None:
        return max(
            attention_vmem_bytes(
                block_q, block_k * (ATTENTION_FWD_K_TILES if kern == "fwd"
                                    else 1),
                d, itemsize, has_mask, major, kern, heads)
            for kern in ("fwd", "dq", "dkv"))
    d_eff = round_up(max(d, 1), LANES)
    bq = round_up(max(block_q, 1), SUBLANES)
    bk = round_up(max(block_k, 1), LANES)
    q_blk, k_blk = ((bq, d_eff), itemsize), ((bk, d_eff), itemsize)
    stat_blk, stat_col = ((heads, 1, bq), 4), ((heads, bq, LANES), 4)
    if kernel == "dkv":
        mq = round_up(max(major or bq, bq), bq)
        return kernel_vmem_bytes(
            operands=[k_blk, k_blk, ((mq, d_eff), itemsize),
                      ((mq, d_eff), itemsize),               # q, dO windows
                      ((heads * mq // bq, 1, bq), 4),
                      ((heads * mq // bq, 1, bq), 4)],
            outputs=[k_blk, k_blk],
            scratch=[((heads, bk, d_eff), 4)] * 2,           # dk, dv
            compute=[((bk, bq), 4)] * 3)
    mk = round_up(max(major or bk, bk), bk)
    kv = [((mk, d_eff), itemsize)] * 2                       # K, V windows
    if has_mask:
        kv.append(((mk // bk, 1, bk), 4))                    # a row a tile
    carry = [((heads, bq, d_eff), 4), stat_col, stat_col]
    if kernel == "fwd":
        return kernel_vmem_bytes(
            operands=[q_blk] + kv, outputs=[q_blk, stat_blk],  # o, lse
            scratch=carry,                                   # acc, m, l
            compute=[((bq, bk), 4)] * 2)
    return kernel_vmem_bytes(                                # dq
        operands=[q_blk, q_blk, q_blk, stat_blk] + kv,       # q, dO, O, lse
        outputs=[q_blk, stat_blk],                           # dq, delta
        scratch=carry,                                       # acc, lse, delta
        compute=[((bq, bk), 4)] * 3)


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int,
                   kernel: str = "fwd", pair: bool = False,
                   residuals: bool = False) -> int:
    """Estimated per-grid-cell VMEM of one grouped-product kernel
    (``grouped_matmul``) at ``tm`` rows, ``tk`` of the contraction and
    ``tn`` of the output's width. ``"fwd"`` (also the plain ``dx``, which
    is the same kernel reading ``w`` transposed): the rows' window, one
    weight window (``pair``: the gated pair's two, and with ``residuals``
    three outputs for one), a float32 accumulator a weight where the
    contraction is cut, and the float32 product tile. ``"dx"``, the gated
    pair's backward: three (tm, tk) row windows in, two out beside ``dx``,
    both weight windows, the prologue's float32 tiles and the product's.
    ``"dw"``: x (tm, tk) and one or two dy (tm, tn) in, a float32 (tk, tn)
    output block each (the accumulator), x stood up for the matrix unit and
    the product tile."""
    tm = round_up(max(tm, 1), SUBLANES)
    tk = round_up(max(tk, 1), LANES)
    tn = round_up(max(tn, 1), LANES)
    n_w = 2 if pair else 1
    if kernel == "dw":
        return kernel_vmem_bytes(
            operands=[((tm, tk), itemsize)] + [((tm, tn), itemsize)] * n_w,
            outputs=[((tk, tn), 4)] * n_w,
            compute=[((tk, tm), itemsize), ((tk, tn), 4)])
    if kernel == "dx":
        return kernel_vmem_bytes(
            operands=[((tm, tk), itemsize)] * 3 + [((tn, tk), itemsize)] * 2,
            outputs=[((tm, tn), itemsize)] + [((tm, tk), itemsize)] * 2,
            compute=[((tm, tk), 4)] * 4 + [((tm, tn), 4)])
    return kernel_vmem_bytes(
        operands=[((tm, tk), itemsize)] + [((tk, tn), itemsize)] * n_w,
        outputs=[((tm, tn), itemsize)] * (3 if pair and residuals else 1),
        scratch=[((tm, tn), 4)] * n_w,
        compute=[((tm, tn), 4)] * n_w)


def ce_vmem_bytes(block_n: int, block_v: int, hidden: int, itemsize: int,
                  has_bias: bool = True) -> int:
    """Estimated per-grid-cell VMEM of the fused-CE forward kernel
    (``cross_entropy.fused_ce_forward``): h/w operand windows (+ the f32
    bias slice and the int32 label broadcast) + the m/l/label-logit
    scratch carries + lse/ll outputs + the f32 logits and probability
    compute tiles."""
    h_eff = round_up(max(hidden, 1), LANES)
    bn = round_up(max(block_n, 1), SUBLANES)
    bv = round_up(max(block_v, 1), LANES)
    ops = [((bn, h_eff), itemsize),             # h window
           ((h_eff, bv), itemsize),             # w window
           ((bn, LANES), 4)]                    # labels (int32 broadcast)
    if has_bias:
        ops.append(((SUBLANES, bv), 4))         # f32 bias slice
    outs = [((bn, LANES), 4), ((bn, LANES), 4)]     # lse / label logit
    scr = [((bn, LANES), 4), ((bn, LANES), 4), ((bn, LANES), 4)]
    comp = [((bn, bv), 4), ((bn, bv), 4)]       # logits and p tiles, f32
    return kernel_vmem_bytes(operands=ops, outputs=outs, scratch=scr,
                             compute=comp)


def embed_gather_vmem_bytes(block_n: int, capacity: int, d: int,
                            itemsize: int) -> int:
    """Estimated per-grid-cell VMEM of the embedding expand-gather
    kernel (``embedding.embed_expand``): the whole unique-row block +
    the int32 index broadcast as operands, the expanded row window as
    output, and the (block_n, capacity) one-hot selection tile the MXU
    contraction holds live. The runtime budget fallback and zoolint's
    static ZL024 check price through this one formula."""
    cap = round_up(max(capacity, 1), LANES)
    d_eff = round_up(max(d, 1), LANES)
    bn = round_up(max(block_n, 1), SUBLANES)
    ops = [((cap, d_eff), itemsize),            # unique-row block (whole)
           ((bn, LANES), 4)]                    # inverse ids (int32)
    outs = [((bn, d_eff), itemsize)]            # expanded rows
    comp = [((bn, cap), itemsize)]              # one-hot selection tile
    return kernel_vmem_bytes(operands=ops, outputs=outs, compute=comp)


def ce_bwd_vmem_bytes(block_n: int, block_v: int, hidden: int,
                      itemsize: int, has_bias: bool = True) -> int:
    """Estimated per-grid-cell VMEM of the fused-CE BACKWARD kernel pair
    (``cross_entropy.fused_ce_backward``): the max of the dh kernel
    (dh output + (block_n, H) f32 accumulator) and the dW/db kernel
    ((H, block_v) f32 accumulator + outputs), each over the shared
    operand set — h/w windows, the int32 label broadcast, the f32
    lse/scale rows and the optional bias slice — plus the f32 logits,
    probability and dlogits compute tiles the tile re-formation holds
    live. The block selectors, the runtime budget clamp and zoolint's
    static ZL024 check all price through this one formula."""
    h_eff = round_up(max(hidden, 1), LANES)
    bn = round_up(max(block_n, 1), SUBLANES)
    bv = round_up(max(block_v, 1), LANES)
    ops = [((bn, h_eff), itemsize),             # h window
           ((h_eff, bv), itemsize),             # w window
           ((bn, LANES), 4),                    # labels (int32 broadcast)
           ((bn, LANES), 4),                    # saved row lse
           ((bn, LANES), 4)]                    # grad scale
    if has_bias:
        ops.append(((SUBLANES, bv), 4))         # f32 bias slice
    comp = [((bn, bv), 4), ((bn, bv), 4), ((bn, bv), 4)]  # logits/p/dl
    dh = kernel_vmem_bytes(
        operands=ops, outputs=[((bn, h_eff), 4)],
        scratch=[((bn, h_eff), 4)], compute=comp)
    dw_outs = [((h_eff, bv), 4)]
    dw_scr = [((h_eff, bv), 4)]
    if has_bias:
        dw_outs.append(((SUBLANES, bv), 4))
        dw_scr.append(((SUBLANES, bv), 4))
    dw = kernel_vmem_bytes(operands=ops, outputs=dw_outs, scratch=dw_scr,
                           compute=comp)
    return max(dh, dw)
