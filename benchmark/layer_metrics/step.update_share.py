"""Share of the device's busy time under ``zoo_opt.update`` (the optimizer's
update, ``apply_updates``, the sharding pins) and ``zoo_opt.guard`` (global
norm, sentinel check, clip). Where XLA fuses a weight's ``dW`` product with
its update into one event, the event carries one name and falls on one side
(``lib/step_ledger.py``)."""

from benchmark.lib import step_ledger


def read(view):
    return step_ledger.share(
        view, lambda led: step_ledger.seconds(led, ("zoo_opt.",)))
