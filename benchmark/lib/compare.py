"""The comparison that decides ``correct`` for a training cell.

Both sides hand in the same record of their first three optimizer steps: each
step's loss, the first gradient (``grad_tree``) with the norm of every leaf
of it, and the norm of every leaf's change after the three steps. ``compare``
reduces them to seven numbers, each held to a limit of its own from the cell's
``limits/<workload>.json``.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Mapping, Tuple

#: leaves whose reference gradient is under this share of the median leaf's
#: move under Adam by round-off alone (a key bias under softmax): they are
#: left out of the change, by this rule and not by name
DEAD_GRADIENT = 1e-3

NUMBERS = ("loss_step1", "loss_step2", "loss_step3", "grad_norm_worst_leaf",
           "grad_error_worst_leaf", "grad_error_median_leaf",
           "change_norm_worst_leaf")


def by_path(tree) -> Dict[str, float]:
    """``{path: value}`` of a tree of scalars."""
    import jax
    import numpy as np
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): float(np.asarray(v)) for p, v in flat}


def leaf_norms(tree) -> Dict[str, float]:
    """``{path: l2 norm}`` of every leaf, computed where the tree lives."""
    import jax
    import jax.numpy as jnp
    return by_path(jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))(
            tree))


def error_norms(got_tree, want_tree) -> Dict[str, float]:
    """``{path: l2 norm of the difference}`` of two trees of one shape.
    Both sides hand their first gradient over as numpy trees on the host;
    they go to the device here, where nothing else is left by then."""
    import jax
    return leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x - y, a, b))(got_tree, want_tree))


def worst_leaf(got: Mapping[str, float], want: Mapping[str, float],
               leaves=None) -> Tuple[float, str]:
    """The widest gap between the two sides' norms of one leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    if set(got) != set(want):
        raise ValueError("the two sides disagree on the parameter leaves: "
                         f"{sorted(set(got) ^ set(want))[:4]}")
    median = statistics.median(want.values())
    worst, where = 0.0, ""
    for k in (want if leaves is None else leaves):
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-30)
        if not gap <= worst:        # a NaN has to win
            worst, where = gap, k
    return worst, where


def numbers(got: Mapping, want: Mapping) -> Dict[str, Tuple[float, str]]:
    """``{name: (value, note)}`` for the seven numbers compared."""
    out: Dict[str, Tuple[float, str]] = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        out[f"loss_step{i + 1}"] = (abs(a - b) / max(abs(b), 1e-30),
                                    f"{a:.6f} vs {b:.6f}")
    out["grad_norm_worst_leaf"] = worst_leaf(got["grad"], want["grad"])
    median = statistics.median(want["grad"].values())
    # the norm of the difference, which random rounding cannot average out
    # of (PERF.md section 2 says why it stands beside the gap of norms)
    errors = {k: err / max(want["grad"][k], median, 1e-30) for k, err in
              error_norms(got["grad_tree"], want["grad_tree"]).items()}
    where = max(errors, key=lambda k: (errors[k] != errors[k], errors[k]))
    out["grad_error_worst_leaf"] = (errors[where], where)
    out["grad_error_median_leaf"] = (statistics.median(errors.values()),
                                     f"{len(errors)} leaves")
    moving = [k for k, v in want["grad"].items()
              if v >= DEAD_GRADIENT * median]
    out["change_norm_worst_leaf"] = worst_leaf(got["change"], want["change"],
                                               moving)
    return out


def compare(got: Mapping, want: Mapping, limits: Mapping[str, float]
            ) -> Tuple[bool, List[Dict]]:
    """``(correct, rows)``; a number with no limit in the cell's file is
    printed and not held (PERF.md says which those are and why)."""
    rows, ok = [], True
    for name, (value, note) in numbers(got, want).items():
        limit = limits.get(name)
        passed = limit is None or value <= limit
        ok = ok and passed
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": passed, "note": note})
    return ok, rows
