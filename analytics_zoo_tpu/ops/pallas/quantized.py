"""Int8 weight-only fused dequant-matmul kernel (SURVEY §2.3; the
reference's int8 story is OpenVINO VNNI on Xeon,
``InferenceModel.scala:622-656``).

``y = x @ (w_q * scale)`` with per-output-column scales, fused so the int8
weights upcast in VMEM tile-by-tile — HBM traffic stays 1 byte/weight, the
point of weight-only quantization. The product is a float32 matmul at
``Precision.HIGHEST``: at default precision the MXU rounds both f32
operands to bf16 (found on a TPU v5e, PR 21: 1.6e-3 of the output's max
against the dequantised product), which the ``x.dtype`` contract does
not allow; what the extra passes cost is not measured. Standalone public
API: the
``pipeline/inference`` int8 predict path currently dequantizes in-jit and
relies on XLA fusing the convert+scale into consumers; this kernel is the
hand-scheduled alternative for callers that matmul against a quantized
table directly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import LANES as _LANES
from .common import SUBLANES as _SUBLANES
from .common import pad_to_multiple
from .common import round_up as _round_up

__all__ = ["int8_matmul"]


def _kernel(x_ref, wq_ref, scale_ref, o_ref):
    """x (BM, K) f32 · wq (K, BN) int8 ∘ scale (1, BN) → o (BM, BN)."""
    w = wq_ref[:].astype(jnp.float32)
    acc = jax.lax.dot_general(x_ref[:].astype(jnp.float32), w,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[:] = (acc * scale_ref[0, :][None, :]).astype(o_ref.dtype)


def int8_matmul(x: jax.Array, w_q: jax.Array, scales: jax.Array,
                block_m: int = 128, block_n: int = 128,
                interpret: Optional[bool] = None) -> jax.Array:
    """``x (M, K) @ dequant(w_q (K, N) int8, scales (N,))`` → (M, N) in
    ``x.dtype``. Equivalent to ``x @ (w_q.astype(f32) * scales)``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, kdim = x.shape
    k2, n = w_q.shape
    if kdim != k2 or scales.shape != (n,):
        raise ValueError(f"shape mismatch: x {x.shape}, w_q {w_q.shape}, "
                         f"scales {scales.shape}")
    # the short-matrix clamp re-lands on the tile floors — a raw min()
    # against an unaligned M/N (m=100 -> block_m=100) hands Mosaic an
    # untileable block on compiled TPU runs; the padding below absorbs
    # the round-up and the [:m, :n] slice drops it again
    block_m = _round_up(min(block_m, max(m, 1)), _SUBLANES)
    block_n = _round_up(min(block_n, max(n, 1)), _LANES)

    xp = pad_to_multiple(x, 0, block_m)
    wp = pad_to_multiple(w_q, 1, block_n)
    sp = pad_to_multiple(scales.reshape(1, n), 1, block_n)

    out = pl.pallas_call(
        _kernel,
        grid=(xp.shape[0] // block_m, wp.shape[1] // block_n),
        in_specs=[
            pl.BlockSpec((block_m, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec((kdim, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]), x.dtype),
        interpret=interpret,
        name="zoo_int8_matmul",
    )(xp, wp, sp)
    return out[:m, :n]
