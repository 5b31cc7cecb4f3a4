"""The dense gated feed-forward layers' share of the device's busy time:
traced time of the operations under the scope ``zoo_ffn.gated`` (a block's
dense layer) and under ``zoo_moe.shared`` (a routed layer's shared expert,
the same three products) over the traced window's busy time. Reads nothing
where the step has neither scope."""

from benchmark.lib import scopes


def read(view):
    tr = view["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    text = scopes.step_text(view)
    spent = sum(scopes.scope_seconds(tr, text, marker)
                for marker in ("zoo_ffn.gated", "zoo_moe.shared"))
    return 100.0 * spent / tr["busy_s"] if spent > 0 else None
