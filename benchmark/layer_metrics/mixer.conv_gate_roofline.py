"""The gate-conv-gate chains' share of their roofline: the least time the
chip's peaks allow for the bytes every ``conv`` layer's chain must move
forward and backward in a step (``lib/kernel_cost_mixer.py``: the same work
whatever implements it, nothing recomputed), over the traced device time of
the operations under the scope ``zoo_conv.gate`` and of any kernel named
``zoo_conv_*``. Reads nothing where the configuration has no ``conv`` layer
or the step has no such scope.

**While the chain is XLA fusions the share is an UPPER bound.** The
numerator is all of the chain's work, the denominator only the events the
compiler NAMES after ``zoo_conv.gate``: a fusion takes one instruction's
name, and where XLA fuses chain instructions into a neighbouring product
the event is booked under ``zoo_conv.out_proj`` / ``in_proj``. In the
cell's step (one traced run, every instruction's time and the compiled
text; my chip run, PR 36) the backward's larger part sits in each layer's
``dy Wout^T`` fusion (37 of its 51 instructions are the chain's), 13.75 ms
a step for the four layers, of which the product alone needs 5.6 ms at the
matrix unit's peak; the events the scope does catch are 16.45 ms by
instruction (``scopes.scope_seconds`` splits a key that instructions
inside and outside the scope share by their count, and reads 15.35). So
where this reads 47.0 % the chain's share lies between 29.3 % (those four
fusions' time beyond the product at peak charged to the chain: 24.6 ms;
23.9 % with the fusions counted whole) and 43.8 %. If the compiler fuses more of the chain
into its neighbours the reading rises without the chain getting faster: a
reading that nears 100 % says the scope no longer catches the chain, not
that the chain is at its roofline. A ``zoo_conv_*`` kernel is counted by
its own name, whole."""

from benchmark.lib import kernel_cost, kernel_cost_mixer as cost, scopes


def read(view):
    tr, cfg, traffic = view["trace"], view["cfg"], view["traffic"]
    layers = list(cfg.get("layer_types", ())).count("conv")
    if tr is None or view["peaks"] is None or not layers:
        return None
    spent = scopes.scope_seconds(tr, scopes.step_text(view), "zoo_conv.gate",
                                 cost.CONV_KERNELS)
    if spent <= 0:
        return None
    rows = traffic["batch"] * traffic["seq"] // view["device"]["count"]
    least = view["steps"] * layers * sum(
        kernel_cost.least_seconds(*cost.gate_chain(
            direction, rows=rows, hidden=cfg["hidden_size"],
            kernel=cfg["conv_L_cache"]), view["peaks"])
        for direction in cost.GATE_TENSORS)
    return 100.0 * least / spent
