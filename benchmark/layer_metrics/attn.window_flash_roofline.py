"""The window / grouped-head flash kernels' share of their roofline: the
least time the chip's peaks allow for every traced call of ``zoo_flash_fwd``,
``_bwd_dq`` and ``_bwd_dkv`` of the decoder configuration over their traced
device time. Window layers' calls carry ``_win`` behind the kernel's name
and are charged the pairs a window sees (``lib/kernel_cost_decoder.py``)."""

from benchmark.lib import kernel_cost, kernel_cost_decoder as cost


def read(view):
    tr, cfg, traffic = view["trace"], view["cfg"], view["traffic"]
    if tr is None or view["peaks"] is None or "sliding_window" not in cfg:
        return None
    shape = dict(batch=traffic["batch"] // view["device"]["count"],
                 q_heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"], seq=traffic["seq"],
                 head_dim=cfg["head_dim"])
    spent = least = 0.0
    for key, secs in tr["op_seconds"].items():
        name = key.split(" ", 1)[0]
        # the longest kernel name first: "zoo_flash_bwd_dq" is no prefix of
        # "zoo_flash_bwd_dkv", and "zoo_flash_fwd" of neither
        kernel = next((k for k in cost.FLASH_TENSORS if k in name), None)
        if kernel is None:
            continue
        window = cfg["sliding_window"] if "_win" in name else None
        flops, moved = cost.flash_call(kernel, window=window, **shape)
        spent += secs
        least += tr["op_calls"][key] * kernel_cost.least_seconds(
            flops, moved, view["peaks"])
    return 100.0 * least / spent if spent > 0 else None
