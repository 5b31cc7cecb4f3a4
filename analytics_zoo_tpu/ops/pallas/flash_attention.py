"""Flash attention — the L0 Pallas TPU kernel behind the attention stack
(SURVEY §2.3; the reference has no custom kernels at all — its attention is
whole-matrix softmax inside ``TransformerLayer.scala:56``/``BERT.scala:66``,
materializing the (T, T) score matrix in HBM).

Forward design: grid (batch*head, q-blocks, k-blocks) with the k dimension
innermost — TPU pallas runs the grid sequentially, so the online-softmax
carry (acc/m/l) lives in VMEM scratch across the k steps of one q block:
initialized at ``ki == 0``, folded per k block, written out (with the row
log-sum-exp for the backward) at the last k block. VMEM per cell is
O(block_q·D + block_k·D) — K/V stream block-by-block, never the whole
sequence — and both matmuls (QK^T, PV) hit the MXU at tile-aligned sizes in
the input dtype (bfloat16 operands run the MXU at full rate; accumulation is
always float32). Causal cells predicate away k blocks strictly right of the
diagonal. An optional per-batch key-padding mask (B, Tk) streams in
(1, block_k) slices — this is the BERT ``attention_mask`` path.

Causal masking is BOTTOM-RIGHT aligned like the XLA oracle
(``ops/attention.py:41``): query i attends keys ``j <= i + (t_kv - t_q)``.
Rows with no visible key (t_q > t_kv tails, or fully-masked rows) return
zeros — the one spot the oracle differs (its -1e9 fill degrades to uniform
weights there).

Backward: the standard two-kernel recompute scheme (no (T, T) tensor is ever
materialized, unlike the r3 XLA-recompute fallback this replaces):
``delta = rowsum(dO·O)`` in XLA, then a dq kernel (grid bh, qi, ki — k
innermost, dq accumulates in VMEM) and a dk/dv kernel (grid bh, ki, qi — q
innermost, dk/dv accumulate in VMEM), each re-forming one (block_q, block_k)
probability tile at a time from the saved log-sum-exp. Memory stays
O(block²) end to end, which is what makes long-context *training* fit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES as _LANES
from .common import SUBLANES as _SUBLANES
from .common import (attention_vmem_bytes, pad_to_multiple, sweep_fastest,
                     vmem_usable_bytes)
from .common import round_up as _round_up

__all__ = ["flash_attention", "select_attention_blocks"]


# ---------------------------------------------------------------------------
# block autotuning: VMEM-budget heuristic + optional one-shot on-device sweep
# (the footprint formula itself is the SHARED estimator in common.py —
# cross_entropy's clamp and zoolint's static ZL024 check price with the
# same function, property-tested in tests/test_pallas.py)
# ---------------------------------------------------------------------------

#: preferred default, swept on a v5e (causal, D=64, T=32k, fwd+bwd):
#: (256, 512) hit 29.3 TF/s vs 21.2 for (256, 256), 23.1 for (512, 512),
#: 24.4-24.9 for k-blocks of 1024/2048 — the larger k block amortizes the
#: per-k-step carry fold without outgrowing VMEM
_PREFERRED_BLOCKS = (256, 512)

#: abstract signature -> (block_q, block_k), resolved once per process
_BLOCK_CACHE: dict = {}

#: back-compat aliases — the estimator and budget constants moved to
#: ``common.py`` so the fused-CE clamp and the zoolint device pass share
#: one formula
_kernel_vmem_bytes = attention_vmem_bytes
from .common import VMEM_BYTES_DEFAULT as _VMEM_BYTES_DEFAULT  # noqa: E402
from .common import VMEM_USABLE_FRACTION as _VMEM_USABLE_FRACTION  # noqa: E402


def select_attention_blocks(t_q: int, t_kv: int, d: int, dtype,
                            causal: bool = False, has_mask: bool = False,
                            budget_bytes: Optional[int] = None):
    """VMEM-budget-aware (block_q, block_k): start from the swept
    ``(256, 512)`` sweet spot, clamp to the sequence lengths, then shrink
    the larger block until the kernel's estimated footprint fits the
    budget. Deterministic — a pure function of the abstract signature, so
    the jit cache is stable."""
    budget = budget_bytes if budget_bytes is not None else \
        vmem_usable_bytes()
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk = _PREFERRED_BLOCKS
    bq = max(_SUBLANES, min(bq, _round_up(max(t_q, 1), _SUBLANES)))
    bk = max(_LANES, min(bk, _round_up(max(t_kv, 1), _LANES)))
    # every shrink step rounds DOWN to the tile floor — halving an
    # already-clamped odd block (bq 56 -> 28, or 200 -> 100) would hand
    # Mosaic an untileable pair on the default path every caller hits
    while (_kernel_vmem_bytes(bq, bk, d, itemsize, has_mask) > budget
           and (bq > _SUBLANES or bk > _LANES)):
        if bk >= 2 * bq and bk > _LANES:
            bk = max(_LANES, bk // 2 // _LANES * _LANES)
        elif bq > _SUBLANES:
            bq = max(_SUBLANES, bq // 2 // _SUBLANES * _SUBLANES)
        else:
            bk = max(_LANES, bk // 2 // _LANES * _LANES)
    return bq, bk


def _sweep_candidates(t_q: int, t_kv: int, d: int, itemsize: int,
                      has_mask: bool, heuristic):
    budget = vmem_usable_bytes()
    out = []
    for bq, bk in (heuristic, (256, 512), (128, 512), (256, 256),
                   (512, 512), (128, 1024)):
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
        rng.normal(size=(b, h, t_q, d)).astype(np.float32), dtype))
    k = jax.device_put(jnp.asarray(
        rng.normal(size=(b, h, t_kv, d)).astype(np.float32), dtype))
    v = jax.device_put(jnp.asarray(
        rng.normal(size=(b, h, t_kv, d)).astype(np.float32), dtype))
    m = (jax.device_put(jnp.ones((b, t_kv), jnp.float32))
         if has_mask else None)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q: jnp.sum(
            _flash(q, k, v, m, causal, block_q, block_k, False)
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


def _record_block_choice(sig: str, choice) -> None:
    try:
        from ...observability import default_registry
        # sig/choice are bounded by the distinct abstract kernel
        # signatures a process compiles (each also a jit cache entry)
        default_registry().gauge(  # zoolint: disable=ZL015 bounded label set
            "zoo_pallas_block_choice",
            "selected pallas kernel block sizes per abstract signature "
            "(1 = active choice)",
            labels={"kernel": "flash_attention", "sig": sig,
                    "choice": f"{choice[0]}x{choice[1]}"}).set(1)
    # metrics must never break the compute path
    except Exception:  # zoolint: disable=ZL007
        pass


def _auto_blocks(q_shape, t_kv: int, dtype, causal: bool, has_mask: bool,
                 interpret: bool):
    """Cached per-signature block choice: the VMEM heuristic, optionally
    refined by the one-shot on-device sweep (compiled TPU runs only — the
    interpreter's timings say nothing about the MXU). The heuristic is a
    pure function of (T, D, dtype, causal, mask), so its cache key drops
    batch/heads — a ragged final batch or an evaluate at a different B
    must not re-resolve (or worse, re-SWEEP: compiling and timing six
    candidates with live training state resident). Only sweep-timed
    entries key on the full shape, since wall time does scale with B·H."""
    b, h, t_q, d = q_shape
    dt = jnp.dtype(dtype)
    from ...common.context import get_zoo_context
    sweep = (bool(get_zoo_context().get("zoo.pallas.block_sweep", False))
             and not interpret and jax.default_backend() == "tpu")
    # the live budget is part of the key — re-initializing the context
    # with zoo.pallas.vmem_budget_mb must take effect at the next call,
    # not silently keep blocks sized for the old budget
    budget = vmem_usable_bytes()
    base = (t_q, t_kv, d, dt.name, causal, has_mask)
    sig = (budget, "sweep", b, h) + base if sweep else (budget,) + base
    cached = _BLOCK_CACHE.get(sig)
    if cached is not None:
        return cached
    choice = select_attention_blocks(t_q, t_kv, d, dt, causal=causal,
                                     has_mask=has_mask,
                                     budget_bytes=budget)
    if sweep:
        choice = _sweep_blocks(b, h, t_q, t_kv, d, dt, causal, has_mask,
                               choice)
    _BLOCK_CACHE[sig] = choice
    # the metric label mirrors the cache key: heuristic entries apply to
    # EVERY batch/head shape at this (T, D, dtype) signature, so baking
    # the first caller's b/h into the label would misdescribe the scope
    _record_block_choice(
        (f"b{b}h{h}" if sweep else "")
        + f"tq{t_q}tk{t_kv}d{d}{dt.name}"
        f"{'c' if causal else ''}{'m' if has_mask else ''}", choice)
    return choice


def _visibility(qi, ki, s_shape, *, t_q, t_kv, offset, causal, mask_blk):
    """The (block_q, block_k) keep-mask of one probability tile: kv padding,
    causal alignment, and the optional key-padding mask row."""
    block_q, block_k = s_shape
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = k_pos < t_kv
    if causal:
        ok = ok & (k_pos <= q_pos + offset)
    if mask_blk is not None:
        # keep-masks are a binary contract (1.0 = attend); >= 1.0 matches
        # the XLA oracle's additive -1e9*(1-mask) on stray soft values too
        # (anything < 1 is effectively hidden there)
        ok = ok & (mask_blk[None, :] >= 1.0)
    return ok


def _fwd_kernel(*refs, scale: float, block_q: int, block_k: int, t_q: int,
                t_kv: int, causal: bool, has_mask: bool, want_lse: bool):
    """Grid cell (bh, qi, ki). q (1, block_q, D); k/v (1, block_k, D);
    [mask (1, SUBLANES, block_k)]; o (1, block_q, D);
    lse (1, block_q, LANES); scratch acc (block_q, D), m/l (block_q, LANES).
    Row/key vectors carry 8-sublane/128-lane broadcast dims — TPU blocks
    need tileable trailing dims (the same layout jax's reference TPU flash
    kernel uses for segment ids and l/m)."""
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    mask_ref = refs[3] if has_mask else None
    rest = refs[3 + int(has_mask):]
    o_ref = rest[0]
    lse_ref = rest[1] if want_lse else None
    acc_ref, m_ref, l_ref = rest[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = t_kv - t_q  # bottom-right causal alignment

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: the first row of this q block sees keys up to
    # qi*block_q + offset; the last row up to (qi+1)*block_q - 1 + offset.
    # Blocks fully beyond the latter contribute nothing — skip their math.
    needed = True
    if causal:
        needed = ki * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(needed)
    def _step():
        # operands stay in the input dtype (bf16 operands = full MXU rate);
        # the product accumulates f32 via preferred_element_type
        q = q_ref[0]
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _visibility(qi, ki, (block_q, block_k), t_q=t_q, t_kv=t_kv,
                         offset=offset, causal=causal,
                         mask_blk=mask_ref[0, 0] if has_mask else None)
        s = jnp.where(ok, s, -jnp.inf)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)
        if want_lse:
            # rows with no visible key: +inf sentinel makes every backward
            # probability exp(s - inf) = 0, matching the zero forward output
            lse = jnp.where(l == 0.0, jnp.inf, m + jnp.log(jnp.where(
                l == 0.0, 1.0, l)))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _prep(q, k, v, mask, block_q, block_k):
    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    # the short-sequence clamp must land back ON the tile floors: a raw
    # min() against an unaligned T (t_q=100 -> block_q=100) hands Mosaic
    # an untileable block on compiled TPU runs — the padding below
    # absorbs the round-up, and the kernels mask past t_q/t_kv
    block_q = _round_up(min(block_q, max(t_q, 1)), _SUBLANES)
    block_k = _round_up(min(block_k, max(t_kv, 1)), _LANES)
    qr = pad_to_multiple(q.reshape(b * h, t_q, d), 1, block_q)
    kr = pad_to_multiple(k.reshape(b * h, t_kv, d), 1, block_k)
    vr = pad_to_multiple(v.reshape(b * h, t_kv, d), 1, block_k)
    mr = None
    if mask is not None:
        mr = pad_to_multiple(mask.astype(jnp.float32), 1, block_k)
        mr = jnp.broadcast_to(mr[:, None, :],
                              (mr.shape[0], _SUBLANES, mr.shape[1]))
    return qr, kr, vr, mr, block_q, block_k


def _flash_fwd(q, k, v, mask, causal: bool, block_q: int, block_k: int,
               interpret: bool, want_lse: bool):
    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    scale = 1.0 / float(d) ** 0.5
    qr, kr, vr, mr, block_q, block_k = _prep(q, k, v, mask, block_q, block_k)
    n_q = qr.shape[1] // block_q
    n_k = kr.shape[1] // block_k
    has_mask = mr is not None

    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, t_q=t_q, t_kv=t_kv,
                               causal=causal, has_mask=has_mask,
                               want_lse=want_lse)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    operands = [qr, kr, vr]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, _SUBLANES, block_k), lambda bh, qi, ki: (bh // h, 0, ki)))
        operands.append(mr)
    out_specs = [pl.BlockSpec((1, block_q, d),
                              lambda bh, qi, ki: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct(qr.shape, q.dtype)]
    if want_lse:
        # inference/primal calls skip the lse output entirely — pallas
        # outputs are opaque to XLA DCE, so an unconditional write would
        # cost real HBM traffic on every no-grad forward
        out_specs.append(pl.BlockSpec((1, block_q, _LANES),
                                      lambda bh, qi, ki: (bh, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct(
            (qr.shape[0], qr.shape[1], _LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
        ],
        interpret=interpret,
        name="zoo_flash_fwd",
    )(*operands)
    out = res[0]  # out_shape is a list either way
    o = out[:, :t_q, :].reshape(b, h, t_q, d)
    return (o, res[1]) if want_lse else o


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale: float, block_q: int, block_k: int,
                   t_q: int, t_kv: int, causal: bool, has_mask: bool):
    """Grid (bh, qi, ki), k innermost: dq accumulates over k blocks."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, mask_ref, dq_ref,
         acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_ref = refs
        mask_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    offset = t_kv - t_q

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    needed = True
    if causal:
        needed = ki * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(needed)
    def _step():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _visibility(qi, ki, (block_q, block_k), t_q=t_q, t_kv=t_kv,
                         offset=offset, causal=causal,
                         mask_blk=mask_ref[0, 0] if has_mask else None)
        lse = lse_ref[0, :, :1]
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, :, :1])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, block_q: int, block_k: int,
                    t_q: int, t_kv: int, causal: bool, has_mask: bool):
    """Grid (bh, ki, qi), q innermost: dk/dv accumulate over q blocks."""
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, mask_ref, dk_ref,
         dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
        mask_ref = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    offset = t_kv - t_q

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    needed = True
    if causal:
        needed = ki * block_k <= (qi + 1) * block_q - 1 + offset

    @pl.when(needed)
    def _step():
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        ok = _visibility(qi, ki, (block_q, block_k), t_q=t_q, t_kv=t_kv,
                         offset=offset, causal=causal,
                         mask_blk=mask_ref[0, 0] if has_mask else None)
        lse = lse_ref[0, :, :1]
        p = jnp.where(ok, jnp.exp(s - lse), 0.0)
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dl_ref[0, :, :1])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, mask, out, lse, g, causal, block_q, block_k,
               interpret):
    b, h, t_q, d = q.shape
    t_kv = k.shape[2]
    scale = 1.0 / float(d) ** 0.5
    qr, kr, vr, mr, block_q, block_k = _prep(q, k, v, mask, block_q, block_k)
    gr = pad_to_multiple(g.reshape(b * h, t_q, d), 1, block_q)
    orr = pad_to_multiple(out.reshape(b * h, t_q, d), 1, block_q)
    # delta_i = sum_d dO_id * O_id — rowwise, cheap in XLA (no (T,T) tensor)
    delta = jnp.sum(gr.astype(jnp.float32) * orr.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, :, None],
                             (*delta.shape, _LANES))
    n_q = qr.shape[1] // block_q
    n_k = kr.shape[1] // block_k
    has_mask = mr is not None

    qspec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0))
    rowspec = pl.BlockSpec((1, block_q, _LANES),
                           lambda bh, qi, ki: (bh, qi, 0))
    mspec = pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda bh, qi, ki: (bh // h, 0, ki))
    operands = [qr, kr, vr, gr, lse, delta] + ([mr] if has_mask else [])

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, t_q=t_q, t_kv=t_kv, causal=causal,
                          has_mask=has_mask),
        grid=(b * h, n_q, n_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec]
                 + ([mspec] if has_mask else []),
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="zoo_flash_bwd_dq",
    )(*operands)

    # dk/dv grid: (bh, ki, qi) — remap the spec index args accordingly
    qspec2 = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    kspec2 = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    rowspec2 = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, ki, qi: (bh, qi, 0))
    mspec2 = pl.BlockSpec((1, _SUBLANES, block_k),
                          lambda bh, ki, qi: (bh // h, 0, ki))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, t_q=t_q, t_kv=t_kv, causal=causal,
                          has_mask=has_mask),
        grid=(b * h, n_k, n_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2]
                 + ([mspec2] if has_mask else []),
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(kr.shape, k.dtype),
                   jax.ShapeDtypeStruct(vr.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="zoo_flash_bwd_dkv",
    )(*operands)

    dq = dq[:, :t_q, :].reshape(b, h, t_q, d)
    dk = dk[:, :t_kv, :].reshape(b, h, t_kv, d)
    dv = dv[:, :t_kv, :].reshape(b, h, t_kv, d)
    dmask = None if mask is None else jnp.zeros_like(mask,
                                                     dtype=jnp.float32)
    return dq, dk, dv, dmask


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, causal, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                      want_lse=False)


def _vjp_fwd(q, k, v, mask, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, mask, causal, block_q, block_k, interpret,
                          want_lse=True)
    return out, (q, k, v, mask, out, lse)


def _vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, mask, out, lse = res
    return _flash_bwd(q, k, v, mask, out, lse, g, causal, block_q, block_k,
                      interpret)


_flash.defvjp(_vjp_fwd, _vjp_bwd)


def _flash_per_data_shard(q, k, v, mask, causal, block_q, block_k,
                          interpret):
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
        return _flash(q, k, v, mask, causal, block_q, block_k, interpret)
    args = (q, k, v) if mask is None else (q, k, v, mask)

    def local(q, k, v, m=None):
        return _flash(q, k, v, m, causal, block_q, block_k, interpret)

    batch = P(mesh_lib.DATA_AXIS)
    return jax.shard_map(local, mesh=mesh, in_specs=(batch,) * len(args),
                         out_specs=batch, check_vma=False)(*args)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Blockwise-softmax attention: q/k/v (B, H, T, D) → (B, H, Tq, D).

    ``mask``: optional per-batch key-padding keep-mask, (B, Tk), a BINARY
    contract: values >= 1.0 attend, anything below is hidden — matching the
    XLA oracle's additive ``-1e9*(1-mask)`` on stray soft values (the BERT
    ``attention_mask``; full (B, H, Tq, Tk) masks stay on the XLA op). Numerically equivalent to
    ``ops.attention.dot_product_attention`` (minus dropout — that path
    stays on the XLA op). Forward and backward are both Pallas kernels with
    O(block²) memory; gradients flow to q/k/v (the mask gets zeros).
    ``interpret`` defaults to auto: compiled on TPU, interpreter elsewhere
    (tests).

    ``block_q``/``block_k`` default to auto selection
    (``select_attention_blocks``): the VMEM-budget-aware heuristic around
    the swept v5e sweet spot (256, 512) — which hit 29.3 TF/s vs 21.2 for
    (256, 256), 23.1 for (512, 512), 24.4-24.9 for k-blocks of 1024/2048
    at causal D=64 T=32k fwd+bwd — shrunk when the abstract signature
    (T, D, dtype, mask) would outgrow VMEM. ``zoo.pallas.block_sweep``
    refines the heuristic with a one-shot on-device sweep, cached per
    signature and surfaced as ``zoo_pallas_block_choice`` info metrics.
    Explicit ints pin the blocks (tests, reproductions)."""
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
    if block_q is None or block_k is None:
        abq, abk = _auto_blocks(q.shape, k.shape[2], q.dtype, causal,
                                mask is not None, interpret)
        block_q = block_q if block_q is not None else abq
        block_k = block_k if block_k is not None else abk
    return _flash_per_data_shard(q, k, v, mask, causal, block_q, block_k,
                                 interpret)
