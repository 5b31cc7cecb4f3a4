"""The flash kernels' share of their roofline: the least time the chip's
peaks allow for every traced call of ``zoo_flash_fwd``, ``_bwd_dq`` and
``_bwd_dkv`` (``lib/kernel_cost.py``) over their traced device time."""

from benchmark.lib import kernel_cost, trace as trace_lib


def read(view):
    tr, cfg, traffic = view["trace"], view["cfg"], view["traffic"]
    if tr is None or view["peaks"] is None:
        return None
    heads = cfg.get("n_head") or cfg.get("num_attention_heads")
    hidden = cfg.get("n_embd") or cfg.get("hidden_size")
    rows = traffic["batch"] // view["device"]["count"]
    costs = {kernel: kernel_cost.flash_call(
        kernel, batch_heads=rows * heads, seq=traffic["seq"],
        head_dim=hidden // heads, causal=cfg["model_type"] != "bert")
        for kernel in kernel_cost.FLASH_PRODUCTS}
    return trace_lib.roofline_share(tr, costs, view["peaks"])
