"""Pallas TPU expand-gather for the sharded embedding engine.

The dedup'd lookup (``ops/sharded_embedding.py``) reduces every batch to
a compact ``(capacity, D)`` unique-row block plus an inverse-index
stream; the last hop — expanding the block back to the ``(N, D)`` row
stream — is a gather XLA lowers to per-row dynamic slices. This kernel
does it as a **one-hot MXU contraction** instead: each ``(block_n, D)``
output tile is ``onehot(inv) @ rows``, a 0/1 matmul that selects exactly
one row per output position (a single nonzero term per sum), while the
memory traffic is a dense, tile-aligned streaming read of the unique
block.

Bit-identical to ``rows[inv]``? Found on a TPU v5e (PR 21; the CPU
interpreter cannot show it): in bfloat16 yes — one MXU pass multiplies
bf16 operands exactly. In float32 NOT at default precision: the MXU
rounds the f32 rows to bf16 (max abs error 1.3e-2 on N(0,1) rows at
capacity 4096, D 64). The contraction therefore asks for
``Precision.HIGHEST`` on f32 tables — Mosaic then splits each row value
into bf16 pieces whose sum is the value, so the selection is exact again
(checked bit for bit by ``chip_smoke.py``) at several MXU passes instead
of one; what that costs against ``jnp.take`` is not measured.

Flag: ``zoo.pallas.embed_gather`` (auto = TPU only). Block sizes come
from the shared VMEM pricing formula
(``common.embed_gather_vmem_bytes``) with the flash-attention shrink
discipline; when even the ``SUBLANES`` floor cannot fit — a huge unique
block — ``jnp.take`` is used instead, with a WARNING.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import LANES as _LANES
from .common import SUBLANES as _SUBLANES
from .common import (embed_gather_vmem_bytes, pad_to_multiple, round_up,
                     vmem_usable_bytes)

__all__ = ["embed_expand", "pallas_embed_gather_enabled"]

log = logging.getLogger("analytics_zoo_tpu.pallas")


def pallas_embed_gather_enabled() -> bool:
    """``zoo.pallas.embed_gather``: auto (TPU only) | true | false — the
    flash-attention flag convention. Routes the dedup'd lookup's
    unique-block → row-stream expansion through the one-hot MXU
    kernel."""
    from ...common.context import tri_state_conf
    flag = tri_state_conf("zoo.pallas.embed_gather")
    if flag == "auto":
        return jax.default_backend() == "tpu"
    return flag


def _select_block_n(n_pad: int, capacity: int, d_pad: int,
                    itemsize: int) -> int:
    """Largest ``block_n`` (≤ 1024, ≥ the sublane floor) whose priced
    footprint fits the usable VMEM budget — the ``_budget_blocks``
    shrink discipline, re-landing on the tile floor every step. A pure
    function of the abstract signature, so the jit cache is stable.
    Returns 0 when even the floor does not fit."""
    budget = vmem_usable_bytes()
    block_n = round_up(min(1024, max(n_pad, 1)), _SUBLANES)
    while (embed_gather_vmem_bytes(block_n, capacity, d_pad,
                                   itemsize) > budget
           and block_n > _SUBLANES):
        block_n = max(_SUBLANES, block_n // 2 // _SUBLANES * _SUBLANES)
    if embed_gather_vmem_bytes(block_n, capacity, d_pad,
                               itemsize) > budget:
        return 0
    return block_n


def _expand_kernel(inv_ref, rows_ref, out_ref, *, capacity: int):
    """One ``(block_n, D)`` output tile: build the ``(block_n,
    capacity)`` one-hot selector from the inverse ids and contract it
    against the whole unique-row block on the MXU."""
    inv = inv_ref[:, :1]
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, (inv_ref.shape[0], capacity), 1) == inv
        ).astype(rows_ref.dtype)
    # f32 rows at default precision would be rounded to bf16 on the MXU
    # (module docstring); bf16 rows are exact in one pass
    precision = (jax.lax.Precision.HIGHEST
                 if rows_ref.dtype == jnp.float32 else None)
    out_ref[...] = jax.lax.dot_general(
        onehot, rows_ref[...], (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def embed_expand(rows: jax.Array, inv: jax.Array,
                 interpret: Optional[bool] = None) -> jax.Array:
    """``rows[inv]`` via the one-hot MXU kernel: ``rows`` is the
    ``(capacity, D)`` unique-row block, ``inv`` the ``(N,)`` int32
    inverse indices; returns ``(N, D)``. Bit-identical to ``jnp.take``
    in bfloat16 and float32 (module docstring) — which it falls back
    to, saying so, when the priced footprint cannot fit even at the
    sublane-floor block size."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = inv.shape[0]
    rp = pad_to_multiple(pad_to_multiple(rows, 0, _LANES), 1, _LANES)
    capacity, d_pad = rp.shape
    itemsize = jnp.dtype(rows.dtype).itemsize
    block_n = _select_block_n(round_up(max(n, 1), _SUBLANES), capacity,
                              d_pad, itemsize)
    if block_n == 0:
        log.warning("embed_expand: a %dx%d %s unique block does not fit "
                    "the VMEM budget at any block size; using jnp.take",
                    capacity, d_pad, jnp.dtype(rows.dtype).name)
        return jnp.take(rows, inv, axis=0)
    n_pad = round_up(max(n, 1), block_n)
    ip = jnp.pad(inv.astype(jnp.int32), (0, n_pad - n))
    inv2 = jnp.broadcast_to(ip[:, None], (n_pad, _LANES))
    kernel = functools.partial(_expand_kernel, capacity=capacity)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((rp.shape[0], rp.shape[1]), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), rows.dtype),
        interpret=interpret,
        name="zoo_embed_expand",
    )(inv2, rp)
    return out[:n, :rows.shape[1]]
