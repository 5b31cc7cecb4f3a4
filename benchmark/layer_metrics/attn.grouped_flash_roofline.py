"""The grouped-head flash kernels' share of their roofline in a
configuration whose attention layers are all full (no window): the least
time the chip's peaks allow for every traced call of ``zoo_flash_fwd``,
``_bwd_dq`` and ``_bwd_dkv`` at the configuration's query / key-value heads
and head size (``lib/kernel_cost_decoder.py::flash_call``) over their traced
device time. Reads nothing where the key/value heads are as many as the
query heads, where the configuration has a ``sliding_window`` (``attn.
window_flash_roofline`` reads those), or where no such kernel ran.

The head size is the configuration's ``head_dim``, or where its published
file has none, as LFM2's, the ``assumed`` one. Hand-worked for LFM2-24B-A2B,
T = 8192, 32 query / 8 key-value heads of 64, batch 4, bf16
(``tests/test_conv.py`` holds the count to these figures):

* pairs: 8192 x 8193 / 2 = 33 558 528.
* forward: 2 x 2 x 33 558 528 x 64 x (4 x 32) = 1 099 645 845 504
  operations; dq 1.5 x that, dkv 2 x: 4.95 T a layer and step, 25.1 ms at
  197 TFLOP/s.
* one query-side tensor: 4 x 32 x 8192 x 64 x 2 = 134 217 728 bytes; one
  key-side tensor a quarter, 33 554 432; one statistic 4 x 32 x 8192 x 4 =
  4 194 304. Forward (q, o; k, v; lse): 2 x 134 217 728 + 2 x 33 554 432 +
  4 194 304 = 339 738 624 bytes (0.41 ms at 819 GB/s against 5.58 ms of
  operations: compute-bound 13 times over).
"""

from benchmark.lib import kernel_cost, kernel_cost_decoder as cost


def shape(cfg, traffic, chips):
    """The flash calls' shape, or None where this metric has nothing to
    read in the configuration."""
    q, kv = cfg.get("num_attention_heads"), cfg.get("num_key_value_heads")
    head_dim = cfg.get("head_dim") or cfg.get("assumed", {}).get("head_dim")
    if not (q and kv and head_dim) or kv >= q or "sliding_window" in cfg:
        return None
    return dict(batch=traffic["batch"] // chips, q_heads=q, kv_heads=kv,
                seq=traffic["seq"], head_dim=head_dim)


def read(view):
    tr = view["trace"]
    if tr is None or view["peaks"] is None:
        return None
    call = shape(view["cfg"], view["traffic"], view["device"]["count"])
    if call is None:
        return None
    spent = least = 0.0
    for key, secs in tr["op_seconds"].items():
        name = key.split(" ", 1)[0]
        kernel = next((k for k in cost.FLASH_TENSORS if k in name), None)
        if kernel is None:
            continue
        flops, moved = cost.flash_call(kernel, **call)
        spent += secs
        least += tr["op_calls"][key] * kernel_cost.least_seconds(
            flops, moved, view["peaks"])
    return 100.0 * least / spent if spent > 0 else None
