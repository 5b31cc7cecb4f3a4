"""Share of the device's busy time that lies under NO ``zoo_*`` scope of the
program: 100 x (1 - seconds the step ledger places / busy time). The
measurement's own blind spot as a number: what the compiler inserted
without a name (prefetch copies, layout changes), what the program runs
outside its scopes, and the window's other programs (``lib/step_ledger.py``).
A reading under 0 means an operation was counted twice (a control-flow
wrapper taken for an event of its own)."""

from benchmark.lib import step_ledger


def read(view):
    return step_ledger.share(view, lambda led: led["busy_s"] - led["placed_s"])
