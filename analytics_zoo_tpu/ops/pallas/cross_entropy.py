"""Fused LM-head cross-entropy kernels — the L0 Pallas pair behind
``ops/fused_cross_entropy.py`` (routing: ``zoo.pallas.cross_entropy``, same
auto-on-TPU convention as the flash-attention kernel).

**Forward** (``fused_ce_forward``): one pass computes, per hidden-state row,
the two scalars the blockwise loss needs — ``logsumexp(h @ W + b)`` and the
label's logit — WITHOUT ever writing a logits tile back to HBM: grid
``(row-blocks, vocab-blocks)`` with the vocab dimension innermost (TPU
pallas runs the grid sequentially, so the online logsumexp carry ``m``/``l``
and the label-logit accumulator live in VMEM scratch across the vocab steps
of one row block, exactly the flash-attention carry scheme). The
``(block_n, block_v)`` logits tile exists only in registers/VMEM; HBM
traffic is the streamed ``W`` tiles plus O(N) outputs, which is what makes
the LM head bandwidth-proportional instead of logits-proportional (Liu &
Abbeel 2023's blockwise-parallel argument applied to the head instead of
attention).

**Backward** (``fused_ce_backward``): the flash-attention two-kernel
recompute scheme applied to the head — each kernel re-forms one
``(block_n, block_v)`` probability tile from the saved row logsumexp
(``p = exp(logits - lse)``, the same compute-dtype rounding as the
forward), builds ``dlogits = (p - onehot) * scale`` in VMEM, and folds it
straight into its product matmul:

* the **dh kernel** (grid row-blocks × vocab-blocks, vocab innermost)
  accumulates ``dlogits @ Wᵀ`` in a ``(block_n, H)`` f32 scratch carry;
* the **dW/db kernel** (grid vocab-blocks × row-blocks, rows innermost)
  accumulates ``hᵀ @ dlogits`` (and the bias row-sum) in an
  ``(H, block_v)`` f32 carry.

The probability tile therefore never reaches HBM in the backward either —
the XLA scan formulation this replaces streams every re-formed tile through
HBM three times (form, dh product, dW product). All matmuls run on the MXU
in the input dtype (bf16 operands at full rate) with float32 accumulation;
block sizes ride the same VMEM-budget heuristic + optional one-shot
on-device sweep (``zoo.pallas.block_sweep``) as flash attention, priced by
the shared estimator (``common.ce_vmem_bytes`` / ``ce_bwd_vmem_bytes``)
zoolint's ZL024 checks against statically.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES as _LANES
from .common import SUBLANES as _SUBLANES
from .common import (ce_bwd_vmem_bytes, ce_vmem_bytes, pad_to_multiple,
                     round_up, sweep_fastest, vmem_usable_bytes)

__all__ = ["fused_ce_forward", "fused_ce_backward", "select_ce_blocks"]


def _budget_blocks(block_n: int, block_v: int, hidden_padded: int,
                   itemsize: int, has_bias: bool, price=ce_vmem_bytes):
    """Shrink ``(block_n, block_v)`` until the kernel's estimated
    footprint — the SAME shared formula the flash-attention autotuner
    prices with (``common.ce_vmem_bytes`` forward /
    ``common.ce_bwd_vmem_bytes`` backward) — fits the usable VMEM
    budget. Deterministic in the abstract signature, so jit caches stay
    stable; every shrink step re-lands on the tile floors (the
    flash-attention discipline)."""
    budget = vmem_usable_bytes()
    while (price(block_n, block_v, hidden_padded, itemsize,
                 has_bias) > budget
           and (block_n > _SUBLANES or block_v > _LANES)):
        if block_v >= 2 * block_n and block_v > _LANES:
            block_v = max(_LANES, block_v // 2 // _LANES * _LANES)
        elif block_n > _SUBLANES:
            block_n = max(_SUBLANES, block_n // 2 // _SUBLANES * _SUBLANES)
        else:
            block_v = max(_LANES, block_v // 2 // _LANES * _LANES)
    return block_n, block_v


def select_ce_blocks(n: int, v: int, hidden: int, dtype,
                     has_bias: bool = True, bwd: bool = False
                     ) -> Tuple[int, int]:
    """VMEM-budget-aware ``(block_n, block_v)`` for the CE kernels: the
    (256, 512) starting point clamped to the problem (rounded back onto
    the tile floors), then shrunk until the priced footprint fits —
    a pure function of the abstract signature, so the jit cache is
    stable. ``bwd`` prices with the backward pair's formula."""
    itemsize = jnp.dtype(dtype).itemsize
    block_n = round_up(min(256, max(n, 1)), _SUBLANES)
    block_v = round_up(min(512, max(v, 1)), _LANES)
    return _budget_blocks(block_n, block_v, round_up(max(hidden, 1), _LANES),
                          itemsize, has_bias,
                          price=ce_bwd_vmem_bytes if bwd else ce_vmem_bytes)


# ---------------------------------------------------------------------------
# block sweep + cache (the flash-attention machinery, for the CE backward)
# ---------------------------------------------------------------------------

#: abstract signature -> (block_n, block_v), resolved once per process
_CE_BLOCK_CACHE: dict = {}


def _ce_sweep_candidates(n: int, v: int, hidden: int, itemsize: int,
                         has_bias: bool, heuristic):
    budget = vmem_usable_bytes()
    out = []
    for bn, bv in (heuristic, (256, 512), (128, 512), (256, 256),
                   (512, 512), (128, 1024)):
        cand = (round_up(min(bn, max(n, 1)), _SUBLANES),
                round_up(min(bv, max(v, 1)), _LANES))
        if cand in out:
            continue
        if ce_bwd_vmem_bytes(*cand, hidden=round_up(max(hidden, 1), _LANES),
                             itemsize=itemsize,
                             has_bias=has_bias) <= budget:
            out.append(cand)
    return out or [heuristic]


def _time_ce_bwd(n, v, hidden, dtype, has_bias, bn, bv,
                 repeats: int = 2) -> float:
    """Best-of-``repeats`` wall seconds for one compiled backward pair at
    the given blocks, on synthetic on-device operands."""
    import time

    import numpy as np
    rng = np.random.default_rng(0)
    h = jax.device_put(jnp.asarray(
        rng.normal(size=(n, hidden)).astype(np.float32), dtype))
    w = jax.device_put(jnp.asarray(
        rng.normal(size=(hidden, v)).astype(np.float32), dtype))
    b = (jax.device_put(jnp.zeros((v,), jnp.float32)) if has_bias
         else None)
    lab = jax.device_put(jnp.asarray(
        rng.integers(0, v, n).astype(np.int32)))
    lse = jax.device_put(jnp.full((n,), float(np.log(v)), jnp.float32))
    scale = jax.device_put(jnp.ones((n,), jnp.float32))

    fn = jax.jit(functools.partial(fused_ce_backward, block_n=bn,
                                   block_v=bv, interpret=False))
    jax.block_until_ready(fn(h, w, b, lab, lse, scale))   # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(h, w, b, lab, lse, scale))
        best = min(best, time.perf_counter() - t0)
    return best


def _record_ce_block_choice(sig: str, choice) -> None:
    try:
        from ...observability import default_registry
        # sig/choice are bounded by the distinct abstract kernel
        # signatures a process compiles (each also a jit cache entry)
        default_registry().gauge(  # zoolint: disable=ZL015 bounded label set
            "zoo_pallas_block_choice",
            "selected pallas kernel block sizes per abstract signature "
            "(1 = active choice)",
            labels={"kernel": "cross_entropy", "sig": sig,
                    "choice": f"{choice[0]}x{choice[1]}"}).set(1)
    # metrics must never break the compute path
    except Exception:  # zoolint: disable=ZL007
        pass


def _auto_ce_bwd_blocks(n: int, v: int, hidden: int, dtype,
                        has_bias: bool, interpret: bool) -> Tuple[int, int]:
    """Cached per-signature (block_n, block_v) for the backward pair:
    the VMEM heuristic, optionally refined by the one-shot on-device
    sweep (``zoo.pallas.block_sweep``; compiled TPU runs only — the
    interpreter's timings say nothing about the MXU)."""
    dt = jnp.dtype(dtype)
    from ...common.context import get_zoo_context
    sweep = (bool(get_zoo_context().get("zoo.pallas.block_sweep", False))
             and not interpret and jax.default_backend() == "tpu")
    budget = vmem_usable_bytes()
    sig = (budget, "ce_bwd", sweep, n, v, hidden, dt.name, has_bias)
    cached = _CE_BLOCK_CACHE.get(sig)
    if cached is not None:
        return cached
    choice = select_ce_blocks(n, v, hidden, dt, has_bias=has_bias,
                              bwd=True)
    if sweep:
        choice = sweep_fastest(
            "CE-backward",
            _ce_sweep_candidates(n, v, hidden, dt.itemsize, has_bias, choice),
            lambda bn, bv: _time_ce_bwd(n, v, hidden, dt, has_bias, bn, bv))
    _CE_BLOCK_CACHE[sig] = choice
    _record_ce_block_choice(
        f"bwd_n{n}v{v}h{hidden}{dt.name}{'b' if has_bias else ''}", choice)
    return choice


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _ce_fwd_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, ll_ref, m_ref,
                   l_ref, a_ref, *, block_n: int, block_v: int, v_total: int,
                   has_bias: bool):
    """Grid cell (ri, vi). h (block_n, H); w (H, block_v);
    [b (SUBLANES, block_v)]; labels (block_n, LANES) int32 broadcast;
    outputs lse/ll (block_n, LANES) f32; scratch m/l/a (block_n, LANES).
    Row vectors carry the LANES broadcast dim — TPU blocks need tileable
    trailing dims (the flash-attention l/m layout)."""
    vi = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        a_ref[:] = jnp.zeros_like(a_ref)

    # operands stay in the input dtype (bf16 = full MXU rate); the product
    # accumulates f32 via preferred_element_type, then rounds to the
    # compute dtype with the bias added in it — Dense.call's exact
    # rounding, which the oracle's logits carry under bf16 policy
    logits = jax.lax.dot_general(h_ref[...], w_ref[...],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(h_ref.dtype)
    if has_bias:
        logits = logits + b_ref[0:1, :].astype(h_ref.dtype)
    logits = logits.astype(jnp.float32)
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    ok = col < v_total              # mask vocab padding out of the lse
    logits = jnp.where(ok, logits, -jnp.inf)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    # padded rows (h = 0, all-real columns) stay finite, but a fully-padded
    # vocab tile is all -inf — guard the exp shift like the flash kernel
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.where(ok, jnp.exp(logits - m_safe), 0.0)
    corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[:, :1] = m_new
    # label logit: at most one column of one tile matches each row's label
    # (padded rows carry label -1 and never match)
    hit = (col == lab_ref[:, :1]) & ok
    a_ref[:, :1] += jnp.sum(jnp.where(hit, logits, 0.0), axis=-1,
                            keepdims=True)

    @pl.when(vi == n_v - 1)
    def _finish():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        ll_ref[...] = jnp.broadcast_to(a_ref[:, :1], ll_ref.shape)


def fused_ce_forward(h: jax.Array, w: jax.Array, b: Optional[jax.Array],
                     labels: jax.Array, block_n: int = 256,
                     block_v: int = 512,
                     interpret: Optional[bool] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Per-row ``(logsumexp, label_logit)`` of ``h @ w [+ b]`` — f32 ``(N,)``
    pairs, no ``(N, V)`` tensor in HBM.

    ``h`` (N, H) in the compute dtype, ``w`` (H, V) pre-cast to match,
    ``b`` (V,) or None, ``labels`` (N,) int32 — rows with label < 0 get a
    zero label logit (the caller masks their loss). ``interpret`` defaults
    to auto: compiled on TPU, interpreter elsewhere (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, hidden = h.shape
    v = w.shape[1]
    # blocks stay on the hardware tile floors (Mosaic needs sublane/lane
    # alignment on compiled TPU runs — the interpreter would not care);
    # the row/vocab padding below absorbs the overshoot. A wide hidden
    # dim then shrinks the blocks until the kernel's estimated footprint
    # fits the usable VMEM budget (shared estimator, common.py).
    block_n = round_up(min(block_n, max(n, 1)), _SUBLANES)
    block_v = round_up(min(block_v, max(v, 1)), _LANES)
    block_n, block_v = _budget_blocks(
        block_n, block_v, round_up(max(hidden, 1), _LANES),
        jnp.dtype(h.dtype).itemsize, b is not None)
    hp = pad_to_multiple(pad_to_multiple(h, 0, block_n), 1, _LANES)
    wp = pad_to_multiple(pad_to_multiple(w, 0, _LANES), 1, block_v)
    lp = jnp.pad(labels.astype(jnp.int32), (0, hp.shape[0] - n),
                 constant_values=-1)
    lab2 = jnp.broadcast_to(lp[:, None], (hp.shape[0], _LANES))
    has_bias = b is not None
    operands = [hp, wp]
    in_specs = [
        pl.BlockSpec((block_n, hp.shape[1]), lambda ri, vi: (ri, 0)),
        pl.BlockSpec((wp.shape[0], block_v), lambda ri, vi: (0, vi)),
    ]
    if has_bias:
        bp = pad_to_multiple(b.astype(jnp.float32).reshape(1, -1), 1, block_v)
        operands.append(jnp.broadcast_to(bp, (_SUBLANES, bp.shape[1])))
        in_specs.append(pl.BlockSpec((_SUBLANES, block_v),
                                     lambda ri, vi: (0, vi)))
    operands.append(lab2)
    in_specs.append(pl.BlockSpec((block_n, _LANES), lambda ri, vi: (ri, 0)))

    kernel = functools.partial(_ce_fwd_kernel, block_n=block_n,
                               block_v=block_v, v_total=v, has_bias=has_bias)
    if not has_bias:
        # keep the kernel's positional layout: splice a no-op bias ref out
        def kernel(h_ref, w_ref, lab_ref, lse_ref, ll_ref, m_ref, l_ref,
                   a_ref):
            return _ce_fwd_kernel(h_ref, w_ref, None, lab_ref, lse_ref,
                                  ll_ref, m_ref, l_ref, a_ref,
                                  block_n=block_n, block_v=block_v,
                                  v_total=v, has_bias=False)
    rowspec = pl.BlockSpec((block_n, _LANES), lambda ri, vi: (ri, 0))
    lse, ll = pl.pallas_call(
        kernel,
        grid=(hp.shape[0] // block_n, wp.shape[1] // block_v),
        in_specs=in_specs,
        out_specs=[rowspec, rowspec],
        out_shape=[jax.ShapeDtypeStruct((hp.shape[0], _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((hp.shape[0], _LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_n, _LANES), jnp.float32),  # label logit
        ],
        interpret=interpret,
        name="zoo_ce_fwd",
    )(*operands)
    return lse[:n, 0], ll[:n, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _ce_bwd_tile(h_ref, w_ref, b_ref, lab_ref, lse_ref, s_ref, vi,
                 block_n: int, block_v: int, v_total: int, has_bias: bool):
    """The shared tile re-formation: one (block_n, block_v) dlogits tile
    rebuilt from the saved row lse — the same compute-dtype rounding as
    the forward, so ``p`` is re-formed bit-for-bit."""
    logits = jax.lax.dot_general(h_ref[...], w_ref[...],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(h_ref.dtype)
    if has_bias:
        logits = logits + b_ref[0:1, :].astype(h_ref.dtype)
    logits = logits.astype(jnp.float32)
    col = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)
    ok = col < v_total
    # pad rows carry lse = +inf: exp(x - inf) = 0 keeps them exactly inert
    p = jnp.where(ok, jnp.exp(logits - lse_ref[:, :1]), 0.0)
    onehot = ((col == lab_ref[:, :1]) & ok).astype(jnp.float32)
    # masked rows carry scale 0, over-range rows carry scale NaN — the
    # matmuls below spread the poison exactly like the XLA formulation
    return (p - onehot) * s_ref[:, :1]


def _ce_bwd_dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, s_ref, dh_ref,
                      acc_ref, *, block_n: int, block_v: int, v_total: int,
                      has_bias: bool):
    """Grid (ri, vi), vocab innermost: dh = dlogits @ Wᵀ accumulates over
    the vocab blocks of one row block in f32 scratch."""
    vi = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dl = _ce_bwd_tile(h_ref, w_ref, b_ref, lab_ref, lse_ref, s_ref, vi,
                      block_n, block_v, v_total, has_bias)
    acc_ref[:] += jax.lax.dot_general(
        dl.astype(h_ref.dtype), w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == n_v - 1)
    def _finish():
        dh_ref[...] = acc_ref[:].astype(dh_ref.dtype)


def _ce_bwd_dw_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, s_ref, dw_ref,
                      db_ref, dw_acc, db_acc, *, block_n: int, block_v: int,
                      v_total: int, has_bias: bool):
    """Grid (vi, ri), rows innermost: dW = hᵀ @ dlogits (and the db
    row-sum) accumulate over the row blocks of one vocab block in f32
    scratch."""
    vi = pl.program_id(0)
    ri = pl.program_id(1)
    n_r = pl.num_programs(1)

    @pl.when(ri == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        if has_bias:
            db_acc[:] = jnp.zeros_like(db_acc)

    dl = _ce_bwd_tile(h_ref, w_ref, b_ref, lab_ref, lse_ref, s_ref, vi,
                      block_n, block_v, v_total, has_bias)
    dw_acc[:] += jax.lax.dot_general(
        h_ref[...], dl.astype(h_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_bias:
        db_acc[:1, :] += jnp.sum(dl, axis=0, keepdims=True)

    @pl.when(ri == n_r - 1)
    def _finish():
        dw_ref[...] = dw_acc[:]
        if has_bias:
            db_ref[...] = db_acc[:]


def fused_ce_backward(h: jax.Array, w: jax.Array, b: Optional[jax.Array],
                      labels: jax.Array, lse: jax.Array, scale: jax.Array,
                      block_n: Optional[int] = None,
                      block_v: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      dh_dtype=None):
    """Fused CE backward — ``(dh, dW, db)`` of the blockwise loss, tile
    re-formation and both product matmuls in VMEM (see module docstring).

    ``h`` (N, H) in the compute dtype, ``w`` (H, V) pre-cast to match,
    ``b`` (V,) f32 or None, ``labels`` (N,) int32 HIT labels (the local
    column index, or -1 for no hit — masked rows and, on the sharded
    path, rows owned by another vocab shard), ``lse`` (N,) f32 saved row
    logsumexp, ``scale`` (N,) f32 per-row dlogits multiplier
    (``fused_cross_entropy._grad_scale``: cotangent / 0 / NaN). Returns
    ``dh`` in ``dh_dtype`` (default ``h.dtype``), ``dW``/``db`` in f32.
    An unset block dim resolves through the per-signature cache +
    optional on-device sweep (``zoo.pallas.block_sweep``); the sweep
    times PAIRS, so both halves of its choice are honored unless the
    caller pins one explicitly."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, hidden = h.shape
    v = w.shape[1]
    has_bias = b is not None
    if block_n is None or block_v is None:
        abn, abv = _auto_ce_bwd_blocks(n, v, hidden, h.dtype, has_bias,
                                       interpret)
        block_n = abn if block_n is None else block_n
        block_v = abv if block_v is None else block_v
    block_n = round_up(min(block_n, max(n, 1)), _SUBLANES)
    block_v = round_up(min(block_v, max(v, 1)), _LANES)
    block_n, block_v = _budget_blocks(
        block_n, block_v, round_up(max(hidden, 1), _LANES),
        jnp.dtype(h.dtype).itemsize, has_bias, price=ce_bwd_vmem_bytes)
    hp = pad_to_multiple(pad_to_multiple(h, 0, block_n), 1, _LANES)
    wp = pad_to_multiple(pad_to_multiple(w, 0, _LANES), 1, block_v)
    n_pad = hp.shape[0] - n
    lp = jnp.pad(labels.astype(jnp.int32), (0, n_pad), constant_values=-1)
    # pad rows: lse = +inf (every re-formed probability underflows to 0)
    # and scale = 0 — exactly the XLA scan's pad-row discipline
    lsep = jnp.pad(lse.astype(jnp.float32), (0, n_pad),
                   constant_values=jnp.inf)
    sp = jnp.pad(scale.astype(jnp.float32), (0, n_pad))
    rows = [jnp.broadcast_to(a[:, None], (hp.shape[0], _LANES))
            for a in (lp, lsep, sp)]
    operands = [hp, wp]
    if has_bias:
        bp = pad_to_multiple(b.astype(jnp.float32).reshape(1, -1), 1,
                             block_v)
        operands.append(jnp.broadcast_to(bp, (_SUBLANES, bp.shape[1])))
    operands.extend(rows)
    n_r = hp.shape[0] // block_n
    n_v = wp.shape[1] // block_v

    def specs(idx_h, idx_w, idx_row):
        out = [pl.BlockSpec((block_n, hp.shape[1]), idx_h),
               pl.BlockSpec((wp.shape[0], block_v), idx_w)]
        if has_bias:
            out.append(pl.BlockSpec((_SUBLANES, block_v), idx_w))
        out.extend(pl.BlockSpec((block_n, _LANES), idx_row)
                   for _ in range(3))
        return out

    static = dict(block_n=block_n, block_v=block_v, v_total=v,
                  has_bias=has_bias)

    dh_kernel = functools.partial(_ce_bwd_dh_kernel, **static)
    if not has_bias:
        def dh_kernel(h_ref, w_ref, lab_ref, lse_ref, s_ref, dh_ref,
                      acc_ref):
            return _ce_bwd_dh_kernel(h_ref, w_ref, None, lab_ref, lse_ref,
                                     s_ref, dh_ref, acc_ref, **static)
    dh = pl.pallas_call(
        dh_kernel,
        grid=(n_r, n_v),
        in_specs=specs(lambda ri, vi: (ri, 0), lambda ri, vi: (0, vi),
                       lambda ri, vi: (ri, 0)),
        out_specs=pl.BlockSpec((block_n, hp.shape[1]),
                               lambda ri, vi: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct(hp.shape, dh_dtype or h.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, hp.shape[1]), jnp.float32)],
        interpret=interpret,
        name="zoo_ce_bwd_dh",
    )(*operands)

    dw_kernel = functools.partial(_ce_bwd_dw_kernel, **static)
    if not has_bias:
        def dw_kernel(h_ref, w_ref, lab_ref, lse_ref, s_ref, dw_ref,
                      dw_acc):
            return _ce_bwd_dw_kernel(h_ref, w_ref, None, lab_ref, lse_ref,
                                     s_ref, dw_ref, None, dw_acc, None,
                                     **static)
    out_specs = [pl.BlockSpec((wp.shape[0], block_v),
                              lambda vi, ri: (0, vi))]
    out_shape = [jax.ShapeDtypeStruct(wp.shape, jnp.float32)]
    scratch = [pltpu.VMEM((wp.shape[0], block_v), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((_SUBLANES, block_v),
                                      lambda vi, ri: (0, vi)))
        out_shape.append(jax.ShapeDtypeStruct((_SUBLANES, wp.shape[1]),
                                              jnp.float32))
        scratch.append(pltpu.VMEM((_SUBLANES, block_v), jnp.float32))
    res = pl.pallas_call(
        dw_kernel,
        grid=(n_v, n_r),
        in_specs=specs(lambda vi, ri: (ri, 0), lambda vi, ri: (0, vi),
                       lambda vi, ri: (ri, 0)),
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        scratch_shapes=scratch,
        name="zoo_ce_bwd_dw",
    )(*operands)

    dh = dh[:n, :hidden]
    dw = res[0][:hidden, :v]
    db = res[1][0, :v] if has_bias else None
    return dh, dw, db
