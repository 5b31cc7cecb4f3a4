"""What a gated short-convolution mixer's gate-conv-gate chain needs for one
layer's step: operations and bytes from its shapes alone, as
``kernel_cost.py`` has them for the kernels of the post-LN block and
``kernel_cost_decoder.py`` for the decoder's.

The chain stands between the mixer's two products: from ``[B, C, u]`` (three
(rows, H) tensors, one product's output) it forms ``v = B * u``, the causal
depthwise convolution ``c[t] = sum_j w[:, j] * v[t - (K - 1) + j]`` with K
taps a channel, and ``y = C * c`` (one (rows, H) tensor, the other product's
input). Whatever implements it has to

* forward: read ``B``, ``C``, ``u`` and write ``y``: 4 tensors, and read the
  taps (H x K, float32);
* backward: read ``B``, ``C``, ``u`` and ``dy`` and write ``dB``, ``dC``,
  ``du``: 7 tensors, read the taps and write their gradient (float32). ``v``
  and ``c`` are formed again from ``B`` and ``u``, which costs no bytes.

A rematerialised block runs the forward a second time; as everywhere in the
benchmark, what is recomputed is not counted. Operations an element of
(rows, H): forward 1 (``B * u``) + 2 K - 1 (the taps' multiply-adds) + 1
(``* C``) = 2 K + 1; backward ``v`` and ``c`` again (2 K), ``dC`` and ``dc``
(2), ``dv`` (2 K - 1), ``dB`` and ``du`` (2), the taps' gradient (2 K):
6 K + 3. They are a few operations a byte: the chain is bound by the memory's
bandwidth on any chip, and its roofline is the bytes'.

Hand-worked, rows = 4 x 8192 = 32768, H = 2048, K = 3, bf16
(``tests/test_conv.py`` holds the count to these figures):

* one tensor: 32768 x 2048 x 2 = 134 217 728 bytes; the taps 2048 x 3 x 4 =
  24 576.
* forward: 4 x 134 217 728 + 24 576 = 536 895 488 bytes; 7 x 67 108 864 =
  469 762 048 operations.
* backward: 7 x 134 217 728 + 2 x 24 576 = 939 573 248 bytes; 21 x
  67 108 864 = 1 409 286 144 operations.
* a layer's step: 1 476 468 736 bytes, 1.803 ms at 819 GB/s (the operations
  9.5 us at 197 TFLOP/s); the cell's four conv layers 7.21 ms a step.
"""

from __future__ import annotations

from typing import Tuple

#: what a trace would name a kernel for the chain by (none exists: the chain
#: is XLA fusions under the scope ``zoo_conv.gate``)
CONV_KERNELS = ("zoo_conv",)

#: (tensors of (rows, H) read or written, times the taps are read or written)
GATE_TENSORS = {"forward": (4, 1), "backward": (7, 2)}


def gate_chain(direction: str, *, rows: int, hidden: int, kernel: int,
               act_bytes: int = 2, tap_bytes: int = 4) -> Tuple[float, float]:
    """``(flops, bytes)`` of one layer's gate-conv-gate chain over ``rows``
    tokens, ``direction`` ``"forward"`` or ``"backward"``."""
    tensors, taps = GATE_TENSORS[direction]
    per_element = 2 * kernel + 1 if direction == "forward" else 6 * kernel + 3
    flops = float(per_element) * rows * hidden
    moved = (tensors * rows * hidden * act_bytes
             + taps * hidden * kernel * tap_bytes)
    return flops, float(moved)
