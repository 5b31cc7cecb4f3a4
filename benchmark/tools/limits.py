"""Read, on the chip and at a cell's own size, what its limits are set from.

    chiprun -- python3 benchmark/tools/limits.py <workload> <n_seeds> [n_fault_seeds] [n_control_seeds]

One process: the program's first three steps on ``n_seeds`` seeds (the lower
readings), then, with the program's state freed, for every seed the float32
reference, the control (the reference with fp8 products, put in the program's place)
and, on the first ``n_fault_seeds`` seeds, the faults a training cell can
have, planted in the reference put in the program's place: half of the batch
left out (and, on several chips, all but one chip's share, which is what a
step without the exchange of gradients computes). A state left unchanged
reads 1 by ``compare``'s measure and needs no run. Training's readings need
no measured window. Every side's record is host data (``three_steps`` hands
its first gradient out as a numpy tree) and the device is emptied between
sides, so each side has the chip to itself as a run's reference has. Writes
``chiprun_out/limits_<workload>.json`` and prints one line per reading.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as bench_run  # noqa: E402
from benchmark.kinds import train  # noqa: E402
from benchmark.lib import compare, reference_run  # noqa: E402


def main(workload: str, n_seeds: int, n_fault_seeds: int = 3,
         n_control_seeds: int = 0) -> None:
    n_control_seeds = n_control_seeds or n_seeds
    import numpy as np

    cell, cfg, traffic, _, _ = bench_run.load_cell(workload)
    train.start_context(int(cell["chips"]),
                        bench_run.read_json(bench_run.HERE, "peaks.json"))
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    batch = int(traffic["batch"])
    rows_per_chip = int(traffic["reference_rows_per_chip"])
    seeds = [3_000_000_019 + 7919 * i for i in range(n_seeds)]

    model = model_lib.build(cfg, traffic)
    batches, got = {}, {}
    for seed in seeds:
        rng = np.random.default_rng(seed)
        batches[seed] = [model_lib.features(cfg, traffic, rng, batch)
                         for _ in range(train.VERIFY_STEPS)]
        t = time.perf_counter()
        got[seed] = train.first_steps(model, model_lib, ref, cfg, seed,
                                      batches[seed], batch)
        print(f"program seed {seed}: {time.perf_counter() - t:.1f} s, "
              f"losses {got[seed]['loss']}", flush=True)
    del model
    train.free_program_state()

    faults = {"half_batch": 0.5}
    if int(cell["chips"]) > 1:
        faults["no_exchange"] = 1.0 / int(cell["chips"])

    def one_side(seed, **kw):
        """One side's record, which is host data; the device is emptied
        behind it, so that the next side starts as a run's reference does."""
        rec = reference_run.three_steps(ref, cfg, seed, batches[seed],
                                        rows_per_chip, **kw)
        train.free_program_state()
        return rec

    readings = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        want = one_side(seed)
        sides = {"program": got[seed]}
        if i < n_control_seeds:
            sides["control_fp8"] = one_side(seed, mode="fp8")
        if i < n_fault_seeds:
            for name, keep in faults.items():
                sides["fault_" + name] = one_side(seed, keep_rows=keep)
        for side, rec in sides.items():
            nums = compare.numbers(rec, want)
            readings.append({"seed": seed, "side": side,
                             **{k: v[0] for k, v in nums.items()},
                             "where": {k: v[1] for k, v in nums.items()}})
            print(f"seed {seed} {side:18s} " + " ".join(
                f"{k}={v[0]:.3e}" for k, v in nums.items()), flush=True)
        print(f"  seed {seed}: reference sides in "
              f"{time.perf_counter() - t:.1f} s; ref losses {want['loss']}",
              flush=True)

    summary = {}
    for name in compare.NUMBERS:
        by_side = {}
        for r in readings:
            by_side.setdefault(r["side"], []).append(r[name])
        summary[name] = {side: {"min": min(v), "max": max(v)}
                         for side, v in by_side.items()}
    print(json.dumps(summary, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/limits_{workload}.json", "w") as f:
        json.dump({"workload": workload, "seeds": seeds,
                   "readings": readings, "summary": summary}, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:5]))
