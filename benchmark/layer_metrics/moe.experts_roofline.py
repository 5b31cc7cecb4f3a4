"""The experts' grouped products' share of their roofline: the least time
the chip's peaks allow for the nine products a routed layer's step makes
over the rows HELD (``zoo_moe_assignments_total{held=true}`` of the window,
from ``model.last_fit_report``), summed over layers and steps, over the
traced device time of the operations under the scope ``zoo_moe.experts``
and of the grouped products' own kernels.
The same work whatever implements it (``lib/kernel_cost_decoder.py``)."""

from benchmark.lib import kernel_cost, kernel_cost_decoder as cost, scopes


def read(view):
    tr, cfg = view["trace"], view["cfg"]
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if tr is None or view["peaks"] is None or not report:
        return None
    spent = scopes.scope_seconds(tr, scopes.step_text(view),
                                 "zoo_moe.experts", cost.EXPERT_KERNELS)
    if spent <= 0:
        return None
    least = 0.0
    for layer in report["layers"].values():
        rows = layer["held"] / view["steps"]
        least += view["steps"] * sum(
            kernel_cost.least_seconds(flops, moved, view["peaks"])
            for flops, moved in cost.expert_products(
                rows, cfg["hidden_size"], cfg["moe_intermediate_size"],
                len(cfg["held_experts"])))
    return 100.0 * least / spent
