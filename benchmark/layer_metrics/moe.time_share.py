"""The routed layers' share of the device's busy time: traced time of the
operations under the scopes ``zoo_moe.*`` (route, dispatch, experts,
combine) and of the grouped products' own kernels over the traced window's
busy time."""

from benchmark.lib import kernel_cost_decoder as cost, scopes


def read(view):
    tr = view["trace"]
    report = (getattr(view["model"], "last_fit_report", None) or {}).get("moe")
    if tr is None or not report or tr["busy_s"] <= 0:
        return None
    return 100.0 * scopes.scope_seconds(
        tr, scopes.step_text(view), "zoo_moe.",
        cost.EXPERT_KERNELS) / tr["busy_s"]
