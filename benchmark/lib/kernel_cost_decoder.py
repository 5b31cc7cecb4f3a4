"""What the decoder configuration's kernels need for one call: operations
and bytes from the call's shapes alone, as ``kernel_cost.py`` has them for
the kernels of the post-LN block.

**Window / grouped-head flash attention.** Each kernel is charged the matrix
products it makes itself (forward QK^T and PV: 2; dq S, dP, dQ: 3; dkv S,
dP, dV, dK: 4), over the (query, key) pairs a causal layer SEES, exactly:
``T (T + 1) / 2`` in a full layer, ``sum_i min(i + 1, W)`` in a window
layer. A product over P pairs of D-wide heads is ``2 P D`` operations a
head. Bytes: the query-side tensors (q, o, dO, dq) at the query heads, the
key-side ones (k, v, dk, dv) at the key/value heads, each read or written
once, and one float32 a query row for each row statistic.

Hand-worked, T = 8192, W = 1024, D = 128, 32 query / 4 key-value heads,
batch 4, bf16:

* pairs: full 8192 x 8193 / 2 = 33 558 528; window 1024 x 1025 / 2 +
  7168 x 1024 = 524 800 + 7 340 032 = 7 864 832 (0.2344 of the full).
* forward, full: 2 x 2 x 33 558 528 x 128 x (4 x 32) = 2 199 291 691 008
  operations (2.2 T); window: 515 429 629 952.
* one query-side tensor: 4 x 32 x 8192 x 128 x 2 = 268 435 456 bytes; one
  key-side tensor: an eighth, 33 554 432; one statistic: 4 x 32 x 8192 x 4
  = 4 194 304. Forward (q, o; k, v; lse): 2 x 268 435 456 + 2 x 33 554 432
  + 4 194 304 = 608 174 080 bytes. dq (q, dO, dq; k, v; lse, delta):
  880 803 840. dkv (q, dO; k, v, dk, dv; lse, delta): 679 477 248.

**The experts' grouped products.** A product of R held rows with the held
experts' (d_in, d_out) matrices is ``2 R d_in d_out`` operations whatever
implements it (rows past the held ones are no work). A step makes nine a
layer: gate, up and down forward, and for each its ``dx`` and its ``dW``;
what a rematerialised block makes again is not counted. Bytes: the G held
matrices once (bf16 where they are read, float32 where ``dW`` is written)
and the rows in and out (bf16).

Hand-worked, R = 32768 (one assignment a token on average: top-8 of 64, 8
held), 2304 x 896, G = 8: a product is 2 x 32768 x 2304 x 896 =
135 291 469 824 operations; gate forward moves 8 x 2304 x 896 x 2 +
32768 x (2304 + 896) x 2 = 33 030 144 + 209 715 200 = 242 745 344 bytes;
the nine products of a layer 1 217 623 228 416 operations.
"""

from __future__ import annotations

from typing import Optional, Tuple

from .kernel_cost import FLASH_PRODUCTS

#: (query-side tensors, key-side tensors, float32 row statistics) a kernel
#: reads and writes
FLASH_TENSORS = {"zoo_flash_fwd": (2, 2, 1), "zoo_flash_bwd_dq": (3, 2, 2),
                 "zoo_flash_bwd_dkv": (2, 4, 2)}


#: what a trace names the experts' grouped products by: XLA's expansion of
#: ``lax.ragged_dot`` today, the Pallas kernels' ``name=`` once there are
EXPERT_KERNELS = ("ragged-dot", "zoo_moe_gmm")


def visible_pairs(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs of one causal sequence: ``j <= i``, and with a
    window ``i - window < j``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_call(kernel: str, *, batch: int, q_heads: int, kv_heads: int,
               seq: int, head_dim: int, window: Optional[int] = None,
               act_bytes: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one call of a flash kernel with grouped heads
    and an optional window."""
    flops = (FLASH_PRODUCTS[kernel] * 2.0 * visible_pairs(seq, window)
             * head_dim * batch * q_heads)
    q_side, kv_side, stats = FLASH_TENSORS[kernel]
    row = batch * seq * head_dim * act_bytes
    moved = (q_side * q_heads * row + kv_side * kv_heads * row
             + stats * batch * q_heads * seq * 4)
    return flops, float(moved)


def grouped_product(rows: float, d_in: int, d_out: int, groups: int, *,
                    weight_bytes: int = 2, act_bytes: int = 2
                    ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one grouped product over ``rows`` held rows."""
    flops = 2.0 * rows * d_in * d_out
    moved = (groups * d_in * d_out * weight_bytes
             + rows * (d_in + d_out) * act_bytes)
    return flops, float(moved)


def expert_products(rows: float, hidden: int, width: int, groups: int):
    """The nine ``(flops, bytes)`` of one routed layer's step over ``rows``
    held rows: gate, up, down forward; each one's ``dx``; each one's
    ``dW`` (written in float32)."""
    out = []
    for d_in, d_out in ((hidden, width), (hidden, width), (width, hidden)):
        out.append(grouped_product(rows, d_in, d_out, groups))      # forward
        out.append(grouped_product(rows, d_out, d_in, groups))      # dx
        out.append(grouped_product(rows, d_in, d_out, groups,
                                   weight_bytes=4))                 # dW
    return out
