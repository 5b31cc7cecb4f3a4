"""Fused blockwise LM-head cross-entropy — the bandwidth-proportional
replacement for the full-logits ``sparse_categorical_crossentropy_from_logits``
training objective (the oracle it is equivalence-tested against in
``tests/test_fused_ce.py``).

The full-logits objective materializes ``(B·T, V)`` fp32 log-probabilities —
2 GB at the 4k long-context bench shape, 8 GB at 32k — three times over
(forward, the softmax backward, the label pick's scatter). This op streams
the hidden states through the vocab projection in row-chunked tiles instead
(Liu & Abbeel 2023's blockwise-parallel formulation applied to the LM head):

* **forward** — per chunk, form the ``(chunk, V)`` logits tile once, fold
  its ``logsumexp`` and the label's logit online, discard the tile. On TPU
  the tile never even reaches HBM: ``ops/pallas/cross_entropy.py`` computes
  both scalars in one VMEM-resident pass (``zoo.pallas.cross_entropy=auto``
  routing, same convention as flash attention).
* **backward** (custom VJP) — re-form one tile at a time from the saved
  row ``logsumexp``: ``dlogits = (softmax - onehot) * g``, then
  ``dW += hᵀ @ dlogits`` and ``dh = dlogits @ Wᵀ`` — both on the MXU in the
  compute dtype (bf16 operands, f32 accumulation), with the ``dW`` carry
  accumulated in f32 across chunks. With the pallas routing on, the tile
  re-formation and both product matmuls run inside the
  ``fused_ce_backward`` kernel pair — the probability tile never reaches
  HBM in the backward either.
* **vocab-sharded** (``sharded_fused_cross_entropy_rows``) — the
  Megatron-LM-style model-parallel form: the head weight shards over the
  ``model`` mesh axis, each rank streams only its ``(chunk, V/n)`` slice
  with a LOCAL online logsumexp, and one ``pmax``+``psum`` pair merges the
  per-rank ``(m, l)`` carries and the label logit (the label's owning
  shard contributes it; every other rank contributes 0). The custom VJP
  re-forms only local tiles, so ``dW`` stays sharded end to end and the
  full-vocab logits row never exists on ANY rank. Label semantics are the
  unsharded op's exactly: labels < 0 are masked out of loss and grads,
  labels >= V NaN-poison their row. Numerics match the unsharded path to
  reassociation-level rounding (the row max, the label logit and every
  per-element term are bit-identical; only the cross-shard denominator
  sum is re-associated by the psum).

Memory is O(chunk·V) end to end (O(chunk·V/n) per rank sharded); FLOPs are
identical to the full-logits path, so the win is pure HBM bandwidth. Labels
< 0 are masked out of the loss and every gradient (padded/ignored
positions); labels >= V poison the row to NaN, exactly as loudly as the
full-logits objective's fill-mode gather — a dataset off-by-one can never
train on silently.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fused_cross_entropy_rows", "fused_sparse_cross_entropy",
           "sharded_fused_cross_entropy_rows",
           "sharded_fused_sparse_cross_entropy", "vocab_shard_count",
           "pallas_ce_enabled", "DEFAULT_CHUNK", "AUTO_MIN_VOCAB"]

#: rows per streamed logits tile: 512·V·4 B of transient f32 per tile
#: (16 MB at V=8192) — small enough to live in cache-adjacent HBM, large
#: enough that the (chunk, V) matmuls stay MXU-shaped
DEFAULT_CHUNK = 512

#: ``zoo.train.fused_ce=auto`` engages the fused loss at/above this head
#: width: below it the full-logits tensor is small, XLA's fused softmax is
#: fine, and the scan's sequentialization would only add dispatch overhead
#: (the flash-attention FLASH_AUTO_MIN_SEQ convention, applied to vocab)
AUTO_MIN_VOCAB = 1024

#: bias value for vocab-padding columns of the sharded path: far enough
#: down that ``exp(pad_logit - anything_real)`` underflows to exactly 0
#: (so pad columns are inert in the logsumexp), finite so no -inf NaN
#: traps, and representable in bfloat16 (the bias is added in the compute
#: dtype, replicating Dense.call's rounding)
_NEG_PAD = -1e30


def _conf(key: str, default):
    from ..common.context import get_zoo_context
    return get_zoo_context().get(key, default)


def pallas_ce_enabled() -> bool:
    """``zoo.pallas.cross_entropy``: auto (TPU only) | true | false — the
    flash-attention flag convention. Covers BOTH the forward kernel and
    the ``fused_ce_backward`` kernel pair."""
    from ..common.context import tri_state_conf
    flag = tri_state_conf("zoo.pallas.cross_entropy")
    if flag == "auto":
        return jax.default_backend() == "tpu"
    return flag


def _pad_rows(a: jax.Array, n_pad: int, value=0):
    if n_pad == 0:
        return a
    cfg = [(0, n_pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, cfg, constant_values=value)


def _chunk_logits(hc, wc, bc):
    """One (chunk, V) logits tile with Dense.call's EXACT rounding: f32
    MXU accumulation, round to the compute dtype, bias added in the
    compute dtype, final f32 upcast — under bf16 policy the oracle's
    logits carry that rounding, and the silent substitution must not be
    more precise than the path it replaces (loss-gate comparability
    across the flag)."""
    logits = jax.lax.dot_general(hc, wc, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(hc.dtype)
    if bc is not None:
        logits = logits + bc
    return logits.astype(jnp.float32)


def _fwd_scan_parts(h, w, b, labels, chunk: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA path: per-row ``(m, l, label_logit)`` — the row max, the
    max-shifted denominator and the label's logit — via a lax.scan over
    row chunks; the (chunk, V) logits tile is the largest live tensor.
    ``lse = m + log(l)`` for the unsharded path; the sharded path merges
    the raw ``(m, l)`` pairs across vocab shards first. Labels < 0 (and
    the sharded path's not-my-shard -1 sentinel) contribute a 0 label
    logit."""
    n, hidden = h.shape
    n_pad = (-n) % chunk
    hp = _pad_rows(h, n_pad)
    lp = _pad_rows(labels, n_pad, value=-1)
    k = hp.shape[0] // chunk
    wc = w.astype(h.dtype)
    bc = None if b is None else b.astype(h.dtype)

    def one(_, inp):
        hc, lc = inp
        logits = _chunk_logits(hc, wc, bc)
        m = jnp.max(logits, axis=-1)
        l = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
        idx = jnp.clip(lc, 0, logits.shape[-1] - 1)
        ll = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
        return None, (m, l, jnp.where(lc >= 0, ll, 0.0))

    _, (m, l, ll) = jax.lax.scan(
        one, None, (hp.reshape(k, chunk, hidden), lp.reshape(k, chunk)))
    return (m.reshape(-1)[:n], l.reshape(-1)[:n], ll.reshape(-1)[:n])


def _fwd_scan(h, w, b, labels, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Per-row (logsumexp, label_logit) — the unsharded finish of
    :func:`_fwd_scan_parts`."""
    m, l, ll = _fwd_scan_parts(h, w, b, labels, chunk)
    return m + jnp.log(l), ll


def _fwd(h, w, b, labels, chunk: int, use_pallas: bool,
         interpret: Optional[bool]):
    if use_pallas:
        from .pallas.cross_entropy import fused_ce_forward
        return fused_ce_forward(h, w.astype(h.dtype), b, labels,
                                block_n=min(chunk, 256),
                                interpret=interpret)
    return _fwd_scan(h, w, b, labels, chunk)


def _grad_scale(labels, g, v: int) -> jax.Array:
    """The per-row dlogits multiplier shared by every backward: the
    incoming cotangent for valid rows, exactly 0 for masked (label < 0)
    rows, NaN for over-range (label >= v) rows — the poison the forward
    already applied, now spread across dW/dh by the matmuls just as the
    full-logits objective's backward would."""
    scale = jnp.where(labels >= 0, g.astype(jnp.float32), 0.0)
    return jnp.where(labels >= v, jnp.nan, scale)


def _bwd_scan(h, w, b, labels, lse, scale, chunk: int,
              dh_dtype=None):
    """Tile-at-a-time backward: re-form each (chunk, V) probability tile
    from the saved row logsumexp, fold ``dW``/``db`` into an f32 scan carry,
    emit ``dh`` per chunk. The dW/dh matmuls run in the compute dtype on
    the MXU with f32 accumulation.

    ``labels`` are the HIT labels (column index or -1 for no local hit —
    the sharded path feeds not-my-shard rows through as -1); ``scale`` is
    the precomputed :func:`_grad_scale` vector. ``dh_dtype`` overrides the
    per-chunk dh rounding (the sharded path keeps f32 across the
    cross-shard psum and rounds once)."""
    n, hidden = h.shape
    v = w.shape[1]
    n_pad = (-n) % chunk
    hp = _pad_rows(h, n_pad)
    lp = _pad_rows(labels, n_pad, value=-1)
    # pad the saved logsumexp with +inf: a padded row's logits are the
    # bare bias (h = 0), and exp(bias - 0) overflows to inf for bias >
    # ~88 — inf * the row's zero grad-scale is NaN, and the dW matmul
    # spreads it everywhere. exp(bias - inf) = 0 keeps pad rows inert.
    lsep = _pad_rows(lse, n_pad, value=jnp.inf)
    sp = _pad_rows(scale, n_pad)
    k = hp.shape[0] // chunk
    wc = w.astype(h.dtype)
    bc = None if b is None else b.astype(h.dtype)
    dh_dtype = dh_dtype or h.dtype

    def one(carry, inp):
        dw, db = carry
        hc, lc, lsec, sc = inp
        # tile re-formation carries the SAME compute-dtype rounding as
        # the forward (see _fwd_scan_parts) so p is re-formed bit-for-bit
        logits = _chunk_logits(hc, wc, bc)
        p = jnp.exp(logits - lsec[:, None])
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (chunk, v), 1)
                  == lc[:, None])
        dl = (p - onehot) * sc[:, None]
        dlc = dl.astype(h.dtype)
        dh = jax.lax.dot_general(dlc, wc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ).astype(dh_dtype)
        dw = dw + jax.lax.dot_general(hc, dlc, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        if db is not None:
            db = db + jnp.sum(dl, axis=0)
        return (dw, db), dh

    dw0 = jnp.zeros((hidden, v), jnp.float32)
    db0 = None if b is None else jnp.zeros((v,), jnp.float32)
    (dw, db), dh = jax.lax.scan(
        one, (dw0, db0),
        (hp.reshape(k, chunk, hidden), lp.reshape(k, chunk),
         lsep.reshape(k, chunk), sp.reshape(k, chunk)))
    dh = dh.reshape(-1, hidden)[:n]
    return dh, dw, db


def _bwd(h, w, b, labels, lse, scale, chunk: int, use_pallas: bool,
         interpret: Optional[bool], dh_dtype=None):
    """Backward dispatcher: the pallas kernel pair when routed (the tile
    re-formation and both product matmuls stay VMEM-resident), else the
    XLA scan. Returns f32 (dh-as-requested, dW, db)."""
    if use_pallas:
        from .pallas.cross_entropy import fused_ce_backward
        # block dims unset on purpose: the kernel's per-signature
        # heuristic/sweep picks the PAIR (the chunk knob governs the XLA
        # scan's streaming granularity, not the kernel's tiling)
        return fused_ce_backward(h, w.astype(h.dtype), b, labels, lse,
                                 scale, interpret=interpret,
                                 dh_dtype=dh_dtype or h.dtype)
    return _bwd_scan(h, w, b, labels, lse, scale, chunk,
                     dh_dtype=dh_dtype)


def _poison_over_range(rows, labels, v):
    """Labels >= V poison their row to NaN — the full-logits oracle's
    fill-mode ``take_along_axis`` fails just as loudly, so a dataset
    off-by-one can never train on silently under either path."""
    return jnp.where(labels >= v, jnp.float32(jnp.nan), rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_rows(h, w, b, labels, chunk, use_pallas, interpret):
    lse, ll = _fwd(h, w, b, labels, chunk, use_pallas, interpret)
    return _poison_over_range(jnp.where(labels >= 0, lse - ll, 0.0),
                              labels, w.shape[1])


def _fused_rows_vjp_fwd(h, w, b, labels, chunk, use_pallas, interpret):
    lse, ll = _fwd(h, w, b, labels, chunk, use_pallas, interpret)
    rows = _poison_over_range(jnp.where(labels >= 0, lse - ll, 0.0),
                              labels, w.shape[1])
    return rows, (h, w, b, labels, lse)


def _fused_rows_vjp_bwd(chunk, use_pallas, interpret, res, g):
    h, w, b, labels, lse = res
    scale = _grad_scale(labels, g, w.shape[1])
    dh, dw, db = _bwd(h, w, b, labels, lse, scale, chunk, use_pallas,
                      interpret)
    # integer primals take float0 cotangents (jax custom_vjp contract)
    dlabels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return (dh.astype(h.dtype), dw.astype(w.dtype),
            None if b is None else db.astype(b.dtype), dlabels)


_fused_rows.defvjp(_fused_rows_vjp_fwd, _fused_rows_vjp_bwd)


def _resolve_chunk(n: int, chunk: Optional[int]) -> int:
    if chunk is None:
        chunk = int(_conf("zoo.train.fused_ce_chunk", DEFAULT_CHUNK)
                    or DEFAULT_CHUNK)
    if chunk <= 0:
        raise ValueError(f"fused-CE chunk must be positive, got {chunk}")
    return max(1, min(chunk, max(n, 1)))


def fused_cross_entropy_rows(hidden: jax.Array, w: jax.Array,
                             b: Optional[jax.Array], labels: jax.Array,
                             chunk: Optional[int] = None,
                             use_pallas: Optional[bool] = None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Per-row cross-entropy of ``softmax(hidden @ w [+ b])`` against int
    ``labels`` — f32 ``(N,)``, rows with label < 0 contribute 0 loss and 0
    gradient; rows with label >= V are NaN (loss and gradient — the
    full-logits objective fails the same way). Differentiable in
    ``hidden``/``w``/``b`` via the tile-streamed custom VJP; the ``(N, V)``
    logits tensor is never materialized.

    With the Pallas kernels on and a mesh of several devices the rows run
    per ``data``/``seq`` shard under ``shard_map`` (the vocab-sharded
    path's own machinery, whatever the ``model`` axis size): a Mosaic
    kernel refuses to lower inside a jit that spans several devices
    ("cannot be automatically partitioned" — found on a four-chip v5e
    host, PR 21). Inside a ``shard_map`` body the rows are per-shard
    already and the call is plain."""
    n = hidden.shape[0]
    labels = labels.reshape(-1).astype(jnp.int32)
    if labels.shape[0] != n:
        raise ValueError(f"fused CE: {n} hidden rows vs "
                         f"{labels.shape[0]} labels")
    if use_pallas is None:
        use_pallas = pallas_ce_enabled()
    if use_pallas:
        from ..parallel import mesh as mesh_lib
        mesh = mesh_lib.global_mesh()
        if mesh.devices.size > 1 and not mesh_lib.in_manual_region():
            return _rows_over_mesh(hidden, w, b, labels, mesh, chunk, True,
                                   interpret)
    return _fused_rows(hidden, w, b, labels, _resolve_chunk(n, chunk),
                       bool(use_pallas), interpret)


def fused_sparse_cross_entropy(y_true, hidden, w, b=None, *,
                               chunk: Optional[int] = None,
                               use_pallas: Optional[bool] = None,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Scalar mean fused CE — the drop-in for
    ``sparse_categorical_crossentropy_from_logits(y, hidden @ w + b)``.
    ``hidden`` may be (..., H); labels broadcast-reshape to the leading
    dims. The mean runs over valid (label >= 0) rows."""
    h2 = hidden.reshape(-1, hidden.shape[-1])
    l2 = jnp.asarray(y_true).reshape(-1).astype(jnp.int32)
    rows = fused_cross_entropy_rows(h2, w, b, l2, chunk=chunk,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
    count = jnp.maximum(jnp.sum((l2 >= 0).astype(jnp.float32)), 1.0)
    return jnp.sum(rows) / count


# ---------------------------------------------------------------------------
# vocab-sharded fused CE (model-parallel head — Megatron-style)
# ---------------------------------------------------------------------------

def vocab_shard_count(mesh=None) -> int:
    """Size of the ``model`` mesh axis — the vocab shard count the
    sharded path splits the head over (1 = no tensor parallelism, the
    unsharded op applies)."""
    from ..parallel import mesh as mesh_lib
    mesh = mesh or mesh_lib.global_mesh()
    return int(mesh.shape[mesh_lib.MODEL_AXIS])


def _localize_labels(labels, off, vs: int):
    """Map global labels onto this rank's column space: the local column
    index when the label lives in ``[off, off + vs)``, else -1 (masked
    rows, other ranks' labels, over-range labels — all of which must
    contribute neither a label logit nor a onehot subtraction HERE;
    over-range poisoning rides the separately-computed grad scale and
    the row-level NaN, both keyed on the GLOBAL label)."""
    loc = labels - off
    mine = (labels >= 0) & (loc >= 0) & (loc < vs)
    return jnp.where(mine, loc, -1)


def _sharded_fwd_local(h, w, b, labels, chunk, use_pallas, interpret):
    """Per-rank forward half: local online logsumexp over this rank's
    vocab slice, then ONE pmax + ONE psum merge the per-rank ``(m, l)``
    carries and the label logit across the ``model`` axis. Runs INSIDE
    shard_map — every array here is the rank-local block; the returned
    (lse, label_logit) rows are identical on every model rank."""
    from ..parallel import mesh as mesh_lib

    vs = w.shape[1]
    rank = jax.lax.axis_index(mesh_lib.MODEL_AXIS)
    lab_loc = _localize_labels(labels, rank * vs, vs)
    if use_pallas:
        from .pallas.cross_entropy import fused_ce_forward
        lse_i, ll_i = fused_ce_forward(h, w.astype(h.dtype), b, lab_loc,
                                       block_n=min(chunk, 256),
                                       interpret=interpret)
        # a finished local lse is the (m, l) pair (lse_i, 1): the merge
        # formula below reduces to logsumexp over the per-rank lse's
        m_i, l_i = lse_i, jnp.ones_like(lse_i)
    else:
        m_i, l_i, ll_i = _fwd_scan_parts(h, w, b, lab_loc, chunk)
    # the pmax/psum pair rides PARALLELISM.md's collective-catalog rows
    # for the `model` axis; adding a collective here needs a row too
    # (ZL025 reconciles both directions).
    m = jax.lax.pmax(m_i, mesh_lib.MODEL_AXIS)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    scaled = jnp.where(jnp.isneginf(m_i), 0.0,
                       l_i * jnp.exp(m_i - m_safe))
    l, ll = jax.lax.psum((scaled, ll_i), mesh_lib.MODEL_AXIS)
    lse = m_safe + jnp.log(jnp.where(l == 0.0, 1.0, l))
    return lse, ll


def _sharded_bwd_local(h, w, b, labels, lse, scale, chunk, use_pallas,
                       interpret):
    """Per-rank backward half: re-form only the local ``(chunk, V/n)``
    tiles (the merged lse re-forms each rank's exact softmax slice).
    dW/db are summed over the row-sharding axes — the data-parallel
    gradient allreduce, landing on the still-sharded ``(H, V/n)`` blocks
    instead of a full ``(H, V)`` tensor — and stay vocab-LOCAL: they
    assemble straight back onto the sharded head params. Only the
    (N, H)-sized dh partials cross the model axis, accumulated in f32
    and rounded once."""
    from ..parallel import mesh as mesh_lib

    vs = w.shape[1]
    rank = jax.lax.axis_index(mesh_lib.MODEL_AXIS)
    lab_loc = _localize_labels(labels, rank * vs, vs)
    dh, dw, db = _bwd(h, w, b, lab_loc, lse, scale, chunk, use_pallas,
                      interpret, dh_dtype=jnp.float32)
    dh = jax.lax.psum(dh, mesh_lib.MODEL_AXIS).astype(h.dtype)
    row_axes = (mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS)
    if db is None:
        dw = jax.lax.psum(dw, row_axes)
        return dh, dw.astype(w.dtype), None
    dw, db = jax.lax.psum((dw, db), row_axes)
    return dh, dw.astype(w.dtype), db.astype(b.dtype)


def _sharded_specs(mesh, had_bias: bool):
    """(row_spec, in_specs for (h, w, [b], labels)) — rows shard over
    (data, seq): the flattened (B·T) layout the training step produces;
    the head weight columns over model."""
    from jax.sharding import PartitionSpec as P

    from ..parallel import mesh as mesh_lib

    row_spec = P((mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS))
    in_specs = (P((mesh_lib.DATA_AXIS, mesh_lib.SEQ_AXIS), None),
                P(None, mesh_lib.MODEL_AXIS)) \
        + ((P(mesh_lib.MODEL_AXIS),) if had_bias else ()) \
        + (row_spec,)
    return row_spec, in_specs


def _sharded_fwd_global(h, w, b, labels, mesh, chunk, use_pallas,
                        interpret):
    """(lse, label_logit) on GLOBAL arrays via shard_map. Both outputs
    are data-sharded rows, replicated across the model axis (every rank
    holds the merged values)."""
    had_bias = b is not None
    row_spec, in_specs = _sharded_specs(mesh, had_bias)
    local = functools.partial(_sharded_fwd_local, chunk=chunk,
                              use_pallas=use_pallas, interpret=interpret)
    if had_bias:
        def run(hh, ww, bb, ll):
            return local(hh, ww, bb, ll)
    else:
        def run(hh, ww, ll):
            return local(hh, ww, None, ll)
    fn = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                       out_specs=(row_spec, row_spec), check_vma=False)
    args = (h, w) + ((b,) if had_bias else ()) + (labels,)
    return fn(*args)


# the custom VJP sits OUTSIDE the shard_map on purpose: both directions
# are explicit shard_map calls whose bodies own every cross-rank
# reduction (the fwd merge psum, the bwd dh-psum and the dW/db
# data-axis allreduce) — nothing is left to shard_map's transpose
# machinery and its unmentioned-axis cotangent conventions
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _sharded_rows(h, w, b, labels, mesh, chunk, v_total, use_pallas,
                  interpret):
    lse, ll = _sharded_fwd_global(h, w, b, labels, mesh, chunk,
                                  use_pallas, interpret)
    return _poison_over_range(jnp.where(labels >= 0, lse - ll, 0.0),
                              labels, v_total)


def _sharded_rows_vjp_fwd(h, w, b, labels, mesh, chunk, v_total,
                          use_pallas, interpret):
    lse, ll = _sharded_fwd_global(h, w, b, labels, mesh, chunk,
                                  use_pallas, interpret)
    rows = _poison_over_range(jnp.where(labels >= 0, lse - ll, 0.0),
                              labels, v_total)
    return rows, (h, w, b, labels, lse)


def _sharded_rows_vjp_bwd(mesh, chunk, v_total, use_pallas, interpret,
                          res, g):
    h, w, b, labels, lse = res
    had_bias = b is not None
    # the grad scale keys on the GLOBAL label: masked rows zero, rows
    # whose label lives on another rank keep the softmax pull (no local
    # onehot), over-range rows NaN on EVERY rank — the matmuls spread the
    # poison across the full sharded dW exactly like the unsharded path
    scale = _grad_scale(labels, g, v_total)
    row_spec, in_specs = _sharded_specs(mesh, had_bias)
    from jax.sharding import PartitionSpec as P

    from ..parallel import mesh as mesh_lib
    w_spec = P(None, mesh_lib.MODEL_AXIS)
    b_spec = P(mesh_lib.MODEL_AXIS)
    local = functools.partial(_sharded_bwd_local, chunk=chunk,
                              use_pallas=use_pallas, interpret=interpret)
    if had_bias:
        def run(hh, ww, bb, ll, ls, sc):
            return local(hh, ww, bb, ll, ls, sc)
        out_specs = (in_specs[0], w_spec, b_spec)
    else:
        def run(hh, ww, ll, ls, sc):
            dh, dw, _ = local(hh, ww, None, ll, ls, sc)
            return dh, dw
        out_specs = (in_specs[0], w_spec)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=in_specs + (row_spec, row_spec),
                       out_specs=out_specs, check_vma=False)
    args = (h, w) + ((b,) if had_bias else ()) + (labels, lse, scale)
    out = fn(*args)
    dh, dw = out[0], out[1]
    db = out[2] if had_bias else None
    dlabels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dh, dw, db, dlabels


_sharded_rows.defvjp(_sharded_rows_vjp_fwd, _sharded_rows_vjp_bwd)


def sharded_fused_cross_entropy_rows(hidden: jax.Array, w: jax.Array,
                                     b: Optional[jax.Array],
                                     labels: jax.Array,
                                     mesh=None,
                                     chunk: Optional[int] = None,
                                     use_pallas: Optional[bool] = None,
                                     interpret: Optional[bool] = None
                                     ) -> jax.Array:
    """Vocab-sharded :func:`fused_cross_entropy_rows`: ``w`` (H, V) is
    split column-wise over the ``model`` mesh axis, rows over
    ``data``/``seq``, and each rank only ever forms ``(chunk, V/n)``
    tiles — the model-parallel LM head whose weight (and weight
    gradient) never fit one chip. Semantics are the unsharded op's:
    label < 0 rows contribute 0 loss/grad, label >= V rows NaN. ``V``
    not divisible by the shard count pads the weight internally (pad
    columns are pinned to a ``-1e30`` bias, exactly inert); row counts
    pad to the row-sharding divisor with masked labels. On a mesh with
    ``model == 1`` this is exactly the unsharded op."""
    from ..parallel import mesh as mesh_lib

    mesh = mesh or mesh_lib.global_mesh()
    if int(mesh.shape[mesh_lib.MODEL_AXIS]) <= 1:
        return fused_cross_entropy_rows(hidden, w, b, labels, chunk=chunk,
                                        use_pallas=use_pallas,
                                        interpret=interpret)
    labels = labels.reshape(-1).astype(jnp.int32)
    if labels.shape[0] != hidden.shape[0]:
        raise ValueError(f"sharded fused CE: {hidden.shape[0]} hidden rows "
                         f"vs {labels.shape[0]} labels")
    if use_pallas is None:
        use_pallas = pallas_ce_enabled()
    return _rows_over_mesh(hidden, w, b, labels, mesh, chunk,
                           bool(use_pallas), interpret)


def _rows_over_mesh(hidden, w, b, labels, mesh, chunk, use_pallas: bool,
                    interpret):
    """The ``shard_map`` form shared by the vocab-sharded op and the
    unsharded op's several-device Pallas route: rows over ``data``/
    ``seq``, head columns over ``model`` (one whole slice when that axis
    has size 1). ``labels`` are validated flat int32."""
    from ..parallel import mesh as mesh_lib
    from .pallas.common import round_up

    n_model = int(mesh.shape[mesh_lib.MODEL_AXIS])
    n = hidden.shape[0]
    v = w.shape[1]

    # rows pad to the row-sharding divisor with label -1 (inert) and are
    # sliced back off below
    row_div = int(mesh.shape[mesh_lib.DATA_AXIS]
                  * mesh.shape[mesh_lib.SEQ_AXIS])
    n_row_pad = (-n) % row_div
    hidden = _pad_rows(hidden, n_row_pad)
    labels = _pad_rows(labels, n_row_pad, value=-1)
    chunk = _resolve_chunk(hidden.shape[0] // row_div, chunk)

    # vocab pads to the shard count; pad columns get zero weights and a
    # _NEG_PAD bias so they are exactly inert in every logsumexp (and
    # their dW/db slots transpose to the sliced-off pad region)
    vp = round_up(v, n_model)
    if vp != v:
        w = jnp.pad(w, ((0, 0), (0, vp - v)))
        bias = b if b is not None else jnp.zeros((v,), jnp.float32)
        b = jnp.pad(bias, (0, vp - v), constant_values=_NEG_PAD)

    rows = _sharded_rows(hidden, w, b, labels, mesh, chunk, v, use_pallas,
                         interpret)
    return rows[:n]


def sharded_fused_sparse_cross_entropy(y_true, hidden, w, b=None, *,
                                       mesh=None,
                                       chunk: Optional[int] = None,
                                       use_pallas: Optional[bool] = None,
                                       interpret: Optional[bool] = None
                                       ) -> jax.Array:
    """Scalar mean vocab-sharded fused CE — the model-parallel drop-in
    for :func:`fused_sparse_cross_entropy` (same reduction: mean over
    valid label >= 0 rows)."""
    h2 = hidden.reshape(-1, hidden.shape[-1])
    l2 = jnp.asarray(y_true).reshape(-1).astype(jnp.int32)
    rows = sharded_fused_cross_entropy_rows(h2, w, b, l2, mesh=mesh,
                                            chunk=chunk,
                                            use_pallas=use_pallas,
                                            interpret=interpret)
    count = jnp.maximum(jnp.sum((l2 >= 0).astype(jnp.float32)), 1.0)
    return jnp.sum(rows) / count
