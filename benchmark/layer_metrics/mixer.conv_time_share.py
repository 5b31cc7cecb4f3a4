"""The short-convolution mixers' share of the device's busy time: traced
time of the operations under the scopes ``zoo_conv.*`` (in_proj, gate,
out_proj) and of any kernel named ``zoo_conv_*`` over the traced window's
busy time. Reads nothing where the step has no such scope."""

from benchmark.lib import kernel_cost_mixer as cost, scopes


def read(view):
    tr = view["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    spent = scopes.scope_seconds(tr, scopes.step_text(view), "zoo_conv.",
                                 cost.CONV_KERNELS)
    return 100.0 * spent / tr["busy_s"] if spent > 0 else None
