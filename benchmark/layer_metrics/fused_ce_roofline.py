"""The fused cross-entropy kernels' share of their roofline: the least time
the chip's peaks allow for every traced call of ``zoo_ce_fwd``, ``_bwd_dh``
and ``_bwd_dw`` (``lib/kernel_cost.py``) over their traced device time."""

from benchmark.lib import kernel_cost, trace as trace_lib


def read(view):
    tr, cfg, traffic = view["trace"], view["cfg"], view["traffic"]
    if tr is None or view["peaks"] is None:
        return None
    hidden = cfg.get("n_embd") or cfg.get("hidden_size")
    rows = traffic["batch"] * traffic["seq"] // view["device"]["count"]
    costs = {kernel: kernel_cost.ce_call(
        kernel, rows=rows, hidden=hidden, vocab=cfg["vocab_size"])
        for kernel in kernel_cost.CE_PRODUCTS}
    return trace_lib.roofline_share(tr, costs, view["peaks"])
