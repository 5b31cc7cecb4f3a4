#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``), the
limits of its comparison (``limits/<cell>.json``), one reader per per-layer
metric (``layer_metrics/<metric>.py``) and the traffic's kind
(``kinds/<kind>.py``), which holds the loop that is measured. The last line
of standard output is the result; without the chips the cell asks for there
is no result and the exit code is 3.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib import reference_run  # noqa: E402


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str):
    """``(cell, cfg, traffic, limits, readers)`` of one ``workloads`` entry."""
    manifest = read_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {', '.join(cells)})")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = read_json(ROOT, entry["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    if int(traffic["chips"]) != int(cell["chips"]):
        raise SystemExit(f"{workload}: the traffic file is laid out for "
                         f"{traffic['chips']} chip(s), the cell asks for "
                         f"{cell['chips']}")
    limits = read_json(HERE, "limits", workload + ".json")["limits"]
    readers = {}
    for metric in manifest["per_layer"]:
        if workload not in metric.get("workloads", [workload]):
            continue
        mod = reference_run.load("layer_metrics", metric["name"])
        readers[metric["name"]] = (mod.read, metric["unit"])
    return cell, cfg, traffic, limits, readers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None,
                    help="directory for the window's per-step host clock "
                         "(diagnosis; not used by the driver)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed is a non-negative whole number")

    cell, cfg, traffic, limits, readers = load_cell(args.workload)
    kind = importlib.import_module("benchmark.kinds." + traffic["kind"])
    try:
        result = kind.run(cell, cfg, traffic, limits, readers,
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_process=T_PROCESS,
                          peaks=read_json(HERE, "peaks.json"),
                          dump=args.dump)
    except kind.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
