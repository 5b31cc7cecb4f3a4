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
from typing import Any, Callable, Dict, Optional, Sequence

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


#: bytes a parameter that ``three_steps`` keeps on the device: while a step's
#: blocks of rows run, weights 4, the summed gradient 4 and the block being
#: added 4, beside one block's temporaries (Adam's moments wait on the host);
#: in the optimizer step, weights 4, the gradient 4 and the moments 8
BYTES_PER_PARAMETER_IN_BLOCKS = 12
BYTES_PER_PARAMETER_IN_STEP = 16


def _to_host(tree):
    """A numpy copy of ``tree`` that keeps no device buffer alive: on the
    CPU ``jax.device_get`` hands out views of the buffers themselves, and a
    buffer that is looked at cannot be given up to the next program."""
    import jax
    return jax.tree.map(lambda a: a if a.base is None else a.copy(),
                        jax.device_get(tree))


def memory_stat(key: str) -> Optional[int]:
    """``memory_stats()[key]`` of the fullest device; None where the backend
    has no such key (the CPU has none). ``bytes_in_use`` counts arrays and
    loaded programs' code, not the temporaries a running program has
    reserved beside them."""
    import jax
    stats = [(d.memory_stats() or {}).get(key) for d in jax.local_devices()]
    stats = [s for s in stats if s is not None]
    return int(max(stats)) if stats else None


def three_steps(ref, cfg: Dict[str, Any], seed: int,
                batches: Sequence, rows_per_chip: int, mode: str = "f32",
                keep_rows: float = 1.0,
                probe: Optional[Callable[[str], None]] = None
                ) -> Dict[str, Any]:
    """Losses, the first gradient with its leaf norms, and the leaf changes
    of ``len(batches)`` steps of the reference at precision ``mode``.

    What is merely kept is kept off the device. The first gradient
    (``grad_tree``) is handed out as a numpy tree on the host; Adam's
    moments wait on the host while a step's blocks of rows run; the change
    is taken at the end, when the weights alone are left, against weights
    made again from the same key. So the device holds
    ``BYTES_PER_PARAMETER_IN_BLOCKS`` bytes a parameter beside one block's
    temporaries, and ``BYTES_PER_PARAMETER_IN_STEP`` in the optimizer step.
    ``device_bytes`` is the largest ``bytes_in_use`` read at the fullest
    points: ``"block"``, a block's gradient just computed, and ``"step"``,
    an optimizer step about to run; ``probe(label)`` is called at the same
    points. ``parameters`` is their number.

    ``keep_rows`` < 1 plants the fault "part of the batch left out, the mean
    taken over the rest" into the reference put in the program's place."""
    import warnings

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
                   donate_argnums=(0, 1, 2))
    subtract = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                       donate_argnums=(0,))

    key = blocks.seed_key(seed)
    params = init(key)
    state = None                # on the host between steps
    per_block = rows_per_chip * len(devices)
    out: Dict[str, Any] = {
        "loss": [], "device_bytes": None,
        "parameters": sum(a.size for a in jax.tree.leaves(params))}

    def fullest(label):
        used = memory_stat("bytes_in_use")
        if used is not None:
            out["device_bytes"] = max(used, out["device_bytes"] or 0)
        if probe is not None:
            probe(label)

    for k, (x, y) in enumerate(batches):
        n = len(y)
        n = max(per_block, int(n * keep_rows) // per_block * per_block)
        total, grads, terms = 0.0, None, 0
        for lo in range(0, n, per_block):
            bx = jax.device_put(_rows(x, lo, lo + per_block), by_rows)
            by = jax.device_put(_rows(y, lo, lo + per_block), by_rows)
            t, g = block_grad(params, bx, by)
            t.block_until_ready()       # read what is kept, not a moment
            fullest("block")            # of the program still running
            total = total + t
            grads = g if grads is None else add(grads, g)
            terms += int(np.prod(np.shape(by)))
            del g           # or it waits beside the next block's
        grads = scale(grads, 1.0 / terms)
        out["loss"].append(float(total) / terms)
        if k == 0:
            out["grad"] = leaf_norms(grads)
            out["grad_tree"] = _to_host(grads)
        state = (jax.jit(blocks.adam_init, out_shardings=everywhere)(params)
                 if state is None else jax.device_put(state, everywhere))
        fullest("step")
        with warnings.catch_warnings():
            # the gradient's buffers are given up with weights and state;
            # three outputs cannot take over four inputs, which JAX remarks
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            params, state = step(params, grads, state)
        del grads
        state = _to_host(state) if k + 1 < len(batches) else None
    # two programs, as before anything was kept off the device: fused into
    # one, the sum of squares runs in another order and reads 4e-7 away
    out["change"] = leaf_norms(subtract(params, init(key)))
    return out
