"""The whole step's share of the chips' peak: forward + backward model FLOPs
per row from the configuration's shapes (``models/<config>.py::
train_flops_per_row``, nothing recomputed) x rows per second of the traced
window, over chips x peak bf16 FLOP/s."""


def read(view):
    if view["peaks"] is None:
        return None
    per_row = view["model_lib"].train_flops_per_row(view["cfg"],
                                                    view["traffic"])
    rows_per_s = view["steps"] * view["traffic"]["batch"] / view["window_s"]
    peak = view["device"]["count"] * view["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_row * rows_per_s / peak
