"""What a kernel's algorithm needs for one call: floating-point operations
and bytes moved, from the call's shapes alone. The per-layer rooflines divide
the least time these allow on the chip's peaks by the kernel's traced time.

Flash attention (Dao et al. 2022) recomputes the score matrix in its
backward kernels; that recomputation is part of the algorithm, so each
kernel is charged the matrix products it makes itself: forward QK^T and PV
(2); dq S, dP, dQ (3); dkv S, dP, dV, dK (4). A causal call needs half of
each T x T product. The fused cross-entropy (logits never stored) makes
h W in its forward (1), and in each backward kernel re-forms the logits and
makes one more product: dh = dlogits W^T (2), dW = h^T dlogits (2).
"""

from __future__ import annotations

from typing import Dict, Tuple

FLASH_PRODUCTS = {"zoo_flash_fwd": 2, "zoo_flash_bwd_dq": 3,
                  "zoo_flash_bwd_dkv": 4}
CE_PRODUCTS = {"zoo_ce_fwd": 1, "zoo_ce_bwd_dh": 2, "zoo_ce_bwd_dw": 2}


def flash_call(kernel: str, *, batch_heads: int, seq: int, head_dim: int,
               causal: bool, act_bytes: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one call of a flash kernel over
    (batch_heads, seq, head_dim) operands."""
    product = 2.0 * batch_heads * seq * seq * head_dim
    flops = FLASH_PRODUCTS[kernel] * product * (0.5 if causal else 1.0)
    tensor = batch_heads * seq * head_dim * act_bytes
    stats = batch_heads * seq * 4           # one float32 per row
    if kernel == "zoo_flash_fwd":           # q k v -> o, logsumexp
        moved = 4 * tensor + stats
    elif kernel == "zoo_flash_bwd_dq":      # q k v do, lse, delta -> dq
        moved = 5 * tensor + 2 * stats
    else:                                   # q k v do, lse, delta -> dk dv
        moved = 6 * tensor + 2 * stats
    return flops, float(moved)


def ce_call(kernel: str, *, rows: int, hidden: int, vocab: int,
            act_bytes: int = 2, weight_bytes: int = 4) -> Tuple[float, float]:
    """``(flops, bytes)`` of one call of a fused cross-entropy kernel over
    ``rows`` hidden states and a (hidden, vocab) head."""
    flops = CE_PRODUCTS[kernel] * 2.0 * rows * hidden * vocab
    h = rows * hidden * act_bytes
    w = hidden * vocab * weight_bytes
    per_row = rows * 4
    if kernel == "zoo_ce_fwd":              # h W b labels -> loss, lse
        moved = h + w + 3 * per_row
    elif kernel == "zoo_ce_bwd_dh":         # h W labels lse -> dh
        moved = 2 * h + w + 2 * per_row
    else:                                   # h W labels lse -> dW db
        moved = h + 2 * w + 2 * per_row
    return flops, float(moved)


def least_seconds(flops: float, moved: float, peaks: Dict[str, float]
                  ) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
