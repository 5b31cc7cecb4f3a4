"""Share of the device's busy time in the pass ``recompute``: the second
forward a rematerialised block runs in the backward pass (JAX writes
``rematted_computation`` into those operations' ``op_name``; the step ledger
reads it, ``lib/step_ledger.py``). What dropping ``remat`` would buy, less
what keeping the activations costs."""

from benchmark.lib import step_ledger


def read(view):
    return step_ledger.share(
        view, lambda led: led["by_pass"].get("recompute", 0.0))
