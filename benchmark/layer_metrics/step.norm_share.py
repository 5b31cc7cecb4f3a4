"""Share of the device's busy time under ``zoo_norm``, all passes: a block's
glue, LayerNorm / RMSNorm and the residual add beside it, and a stack's
final norm (``lib/step_ledger.py``)."""

from benchmark.lib import step_ledger


def read(view):
    return step_ledger.share(
        view, lambda led: led["by_scope"].get("zoo_norm", 0.0))
