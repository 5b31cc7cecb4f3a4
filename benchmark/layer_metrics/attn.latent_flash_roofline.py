"""The latent-attention layers' flash kernels' share of their roofline: the
least time the chip's peaks allow for every traced call of ``zoo_flash_fwd``,
``_bwd_dq`` and ``_bwd_dkv``, over their traced device time. Reads nothing
where the configuration has no latent attention, or where no such kernel ran.

The work is counted from the call's shapes alone, the same whatever
implements it. Training computes MLA's expanded form, so a call is plain
causal attention over ``n`` query and ``n`` key/value heads (no grouping:
the one rotary key head is broadcast into every key head before the call)
whose keys are ``qk_nope_head_dim + qk_rope_head_dim`` wide and whose values
``v_head_dim``; the program hands the call to the flash kernels where the two
widths are equal (GLM-4.7-Flash: 192 + 64 = 256 = 256), and
``kernel_cost_decoder.flash_call`` counts that case: each kernel is charged
the products it makes itself (forward 2, dq 3, dkv 4) over the
``T (T + 1) / 2`` visible pairs, every tensor read or written once.

Hand-worked, T = 8192, 20 heads of 256, batch 4, bf16
(``tests/test_latent.py`` holds the count to these figures):

* pairs: 8192 x 8193 / 2 = 33 558 528.
* forward: 2 products x 2 x 33 558 528 x 256 x (4 x 20) =
  2 749 114 613 760 operations (2.75 T); dq 1.5 x that,
  4 123 671 920 640; dkv 2 x, 5 498 229 227 520. The three: 12.37 T a
  layer and step, 62.8 ms at 197 TFLOP/s (the forward alone 13.95 ms).
* one tensor: 4 x 20 x 8192 x 256 x 2 = 335 544 320 bytes; one statistic:
  4 x 20 x 8192 x 4 = 2 621 440. Forward (q, o; k, v; lse):
  4 x 335 544 320 + 2 621 440 = 1 344 798 720 bytes (1.64 ms at 819 GB/s:
  compute-bound 8.5 times over). dq (q, dO, dq; k, v; lse, delta):
  5 x 335 544 320 + 2 x 2 621 440 = 1 682 964 480. dkv (q, dO; k, v, dk,
  dv; lse, delta): 6 x 335 544 320 + 5 242 880 = 2 018 508 800.
"""

from benchmark.lib import kernel_cost, kernel_cost_decoder as cost


def head_dim(cfg):
    """The one head size of the configuration's flash calls, or None where
    keys and values differ in width (such calls stay on the XLA op)."""
    qk = cfg.get("qk_nope_head_dim", 0) + cfg.get("qk_rope_head_dim", 0)
    return qk if qk and qk == cfg.get("v_head_dim") else None


def read(view):
    tr, cfg, traffic = view["trace"], view["cfg"], view["traffic"]
    if tr is None or view["peaks"] is None or head_dim(cfg) is None:
        return None
    heads = cfg["num_attention_heads"]
    batch = traffic["batch"] // view["device"]["count"]
    spent = least = 0.0
    for key, secs in tr["op_seconds"].items():
        name = key.split(" ", 1)[0]
        kernel = next((k for k in cost.FLASH_TENSORS if k in name), None)
        if kernel is None:
            continue
        flops, moved = cost.flash_call(
            kernel, batch=batch, q_heads=heads, kv_heads=heads,
            seq=traffic["seq"], head_dim=head_dim(cfg))
        spent += secs
        least += tr["op_calls"][key] * kernel_cost.least_seconds(
            flops, moved, view["peaks"])
    return 100.0 * least / spent if spent > 0 else None
