#!/usr/bin/env python3
"""What a configuration costs in device memory, before anything is built.

    python3 benchmark/tools/size.py <config> <traffic>

``<config>`` is a name under ``configs/`` or the path of a configuration's
file (a scratch cut that is no cell yet); ``<traffic>`` a name under
``traffic/``. Runs on the CPU and allocates nothing: the parameters are
counted by ``jax.eval_shape`` over the reference's ``init_params``. Prints
their number, what ``lib/reference_run.py::three_steps`` keeps on the device
for them (its documented bytes a parameter: while a step's blocks of rows
run, beside one block's float32 temporaries, which are not counted here and
have to fit beside them; and in the optimizer step), and the program's
share: float32 weights and AdamW's two moments, 12 bytes a parameter, and 4
more for the gradient inside a step (``fit`` holds a second copy of the 12
today: PERF.md section 7).
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark.lib import reference_run  # noqa: E402

PROGRAM_BYTES = {"weights_and_adam": 12, "gradient": 4}


def read_config(config: str):
    path = config if os.path.isfile(config) else os.path.join(
        HERE, "configs", config + ".json")
    with open(path) as f:
        return json.load(f)


def count(cfg) -> int:
    """The reference's parameters, leaf by leaf, from shapes alone."""
    import jax
    ref = reference_run.load("reference", cfg["reference"])
    shapes = jax.eval_shape(lambda key: ref.init_params(cfg, key),
                            jax.random.key(0))
    return sum(int(a.size) for a in jax.tree.leaves(shapes))


def size(cfg, traffic) -> dict:
    n = count(cfg)
    blocks = int(traffic["batch"]) // (
        int(traffic["reference_rows_per_chip"]) * int(traffic["chips"]))
    in_blocks = reference_run.BYTES_PER_PARAMETER_IN_BLOCKS
    if blocks == 1:     # no sum for the block's gradient to be added to
        in_blocks -= 4
    program = sum(PROGRAM_BYTES.values())
    return {"parameters": n, "reference_blocks_a_step": blocks,
            "reference_bytes_in_blocks": in_blocks * n,
            "reference_bytes_in_step":
                reference_run.BYTES_PER_PARAMETER_IN_STEP * n,
            "program_bytes": program * n}


def main(config: str, traffic_name: str) -> None:
    cfg = read_config(config)
    with open(os.path.join(HERE, "traffic", traffic_name + ".json")) as f:
        traffic = json.load(f)
    s = size(cfg, traffic)
    n = s["parameters"]
    print(f"{cfg['name']} under {traffic_name}: {n} parameters "
          f"({n / 1e6:.1f} M)")
    print(f"  reference, {s['reference_blocks_a_step']} block(s) of rows a "
          f"step: {s['reference_bytes_in_blocks'] // n} bytes a parameter = "
          f"{s['reference_bytes_in_blocks'] / 1e9:.2f} GB beside one block's "
          f"float32 temporaries; {s['reference_bytes_in_step'] // n} = "
          f"{s['reference_bytes_in_step'] / 1e9:.2f} GB in the optimizer step")
    print(f"  program: {PROGRAM_BYTES['weights_and_adam']} + "
          f"{PROGRAM_BYTES['gradient']} bytes a parameter = "
          f"{s['program_bytes'] / 1e9:.2f} GB beside a step's activations")
    print(json.dumps(s))


if __name__ == "__main__":
    if len(sys.argv) != 3:
        raise SystemExit(__doc__)
    os.environ["JAX_PLATFORMS"] = "cpu"
    main(sys.argv[1], sys.argv[2])
