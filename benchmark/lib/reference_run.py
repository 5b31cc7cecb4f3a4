"""Drive a configuration's plain reference through the first optimizer steps.

The reference sees only what the benchmark made from the seed: the weights
(its own ``init_params``), the rows of each step, and the optimizer's
published constants. Gradients are summed over blocks of rows, a block being
``rows_per_chip`` rows on each device, so that float32 attention at the
timed sequence length fits beside nothing else on the chip.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict, List, Sequence

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``, found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rows(tree, lo, hi):
    import jax
    return jax.tree.map(lambda a: a[lo:hi], tree)


def three_steps(ref, cfg: Dict[str, Any], seed: int,
                batches: Sequence, rows_per_chip: int, mode: str = "f32",
                keep_rows: float = 1.0) -> Dict[str, Any]:
    """Losses, the first gradient with its leaf norms, and the leaf changes
    of ``len(batches)`` steps of the reference at precision ``mode``.

    ``keep_rows`` < 1 plants the fault "part of the batch left out, the mean
    taken over the rest" into the reference put in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .compare import leaf_norms

    blocks = ref.B
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("rows",))
    by_rows = NamedSharding(mesh, P("rows"))
    everywhere = NamedSharding(mesh, P())
    opt = cfg["assumed"]["optimizer"]

    init = jax.jit(lambda key: ref.init_params(cfg, key),
                   out_shardings=everywhere)
    params0 = init(blocks.seed_key(seed))

    def block_grad(params, x, y):
        (total, _), grads = jax.value_and_grad(
            lambda p: ref.loss_sum(p, x, y, cfg, mode), has_aux=True)(params)
        return total, grads
    block_grad = jax.jit(block_grad, out_shardings=everywhere)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda a: a * s, t),
                    donate_argnums=(0,))
    step = jax.jit(lambda p, g, s: blocks.adam_step(p, g, s, opt),
                   donate_argnums=(0, 2))

    params = jax.tree.map(jnp.copy, params0)
    state = jax.jit(blocks.adam_init, out_shardings=everywhere)(params)
    per_block = rows_per_chip * len(devices)
    out: Dict[str, Any] = {"loss": []}
    for k, (x, y) in enumerate(batches):
        n = len(y)
        n = max(per_block, int(n * keep_rows) // per_block * per_block)
        total, grads, terms = 0.0, None, 0
        for lo in range(0, n, per_block):
            bx = jax.device_put(_rows(x, lo, lo + per_block), by_rows)
            by = jax.device_put(_rows(y, lo, lo + per_block), by_rows)
            t, g = block_grad(params, bx, by)
            total = total + t
            grads = g if grads is None else add(grads, g)
            terms += int(np.prod(np.shape(by)))
        grads = scale(grads, 1.0 / terms)
        out["loss"].append(float(total) / terms)
        if k == 0:
            out["grad"] = leaf_norms(grads)
            out["grad_tree"] = jax.tree.map(jnp.copy, grads)
        params, state = step(params, grads, state)
    out["change"] = leaf_norms(jax.jit(
        lambda a, b: jax.tree.map(jnp.subtract, a, b))(params, params0))
    return out
