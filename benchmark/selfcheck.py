#!/usr/bin/env python3
"""The yardstick checked against figures worked by hand. Run by hand and in
the CPU rehearsal (``python3 benchmark/selfcheck.py``); not part of ``tests/``.

1. ``lib/trace.py`` on the small trace recorded on a v5e
   (``data/tiny.xplane.pb``, made by ``tools/tiny_trace.py``: 20 dependent
   1024 x 1024 bf16 products in 4 bursts with 20 ms sleeps between) gives
   the busy time, the kernel time and the call count that were read off it
   by hand when it was recorded.
2. ``lib/kernel_cost.py`` gives the hand-worked operations and bytes of the
   flash and fused-CE kernels at the cells' shapes.
3. The last line of a CPU rehearsal of a tiny cell has exactly the
   contract's keys, and the cells' files resolve by name.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.lib import kernel_cost, trace  # noqa: E402


def close(got, want, rel):
    assert abs(got - want) <= rel * abs(want), (got, want)


def check_trace():
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        known = json.load(f)
    red = trace.reduce_file(os.path.join(HERE, "data", "tiny.xplane.pb"))
    assert red is not None, "no TPU plane in the recorded trace"
    secs, calls = trace.kernel_seconds(red, known["kernel_marker"])
    assert calls == known["calls"], (calls, known["calls"])
    close(secs, known["kernel_s"], 1e-9)
    close(red["busy_s"], known["busy_s"], 1e-9)
    assert red["busy_s"] < red["device_span_s"] < known["wall_s"]
    # a 1024^3 bf16 product: 2^31 operations, 10.9 us at the v5e's peak
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = trace.roofline_share(
        red, {known["kernel_marker"]: (2.0 * 1024 ** 3, 3 * 2.0 * 1024 ** 2)},
        peaks)
    close(share, 100 * calls * (2.0 * 1024 ** 3 / 197e12) / secs, 1e-9)
    assert trace.roofline_share(red, {"zoo_flash_fwd": (1.0, 1.0)},
                                peaks) is None
    # union, not sum: overlapping intervals count once, gaps are returned
    busy, gaps = trace.union_ns([(0, 10), (5, 20), (30, 40)])
    assert (busy, gaps) == (30, [(20, 30)])
    print(f"trace: {calls} calls, kernel {secs * 1e3:.3f} ms, busy "
          f"{red['busy_s'] * 1e3:.3f} ms of {red['device_span_s'] * 1e3:.1f} ms")


def check_costs():
    # gpt1_train_s4096: 8 rows x 12 heads of 64 at 4096, causal
    f, b = kernel_cost.flash_call("zoo_flash_fwd", batch_heads=96, seq=4096,
                                  head_dim=64, causal=True)
    assert f == 2 * (2 * 96 * 4096 * 4096 * 64) / 2 == 206158430208.0
    assert b == 4 * (96 * 4096 * 64 * 2) + 96 * 4096 * 4 == 202899456.0
    f_dq, _ = kernel_cost.flash_call("zoo_flash_bwd_dq", batch_heads=96,
                                     seq=4096, head_dim=64, causal=True)
    f_dkv, _ = kernel_cost.flash_call("zoo_flash_bwd_dkv", batch_heads=96,
                                      seq=4096, head_dim=64, causal=True)
    assert (f_dq, f_dkv) == (1.5 * f, 2.0 * f)
    # one step of the cell: 12 layers x (2 + 3 + 4) causal products
    close(12 * (f + f_dq + f_dkv), 1.113e13, 1e-3)
    # the fused CE over 32768 rows x 768 x 40478
    f, b = kernel_cost.ce_call("zoo_ce_fwd", rows=32768, hidden=768,
                               vocab=40478)
    assert f == 2.0 * 32768 * 768 * 40478 == 2037324447744.0
    assert b == 32768 * 768 * 2 + 768 * 40478 * 4 + 3 * 32768 * 4
    total = sum(kernel_cost.ce_call(k, rows=32768, hidden=768,
                                    vocab=40478)[0]
                for k in kernel_cost.CE_PRODUCTS)
    assert total == 5 * f
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    close(kernel_cost.least_seconds(total, 0.0, peaks), 0.05171, 1e-3)
    # memory-bound side of the roofline
    assert kernel_cost.least_seconds(1.0, 819e9, peaks) == 1.0
    print("kernel costs: flash and fused CE match the hand-worked figures")


def check_result_line():
    from benchmark import run as bench_run
    from benchmark.kinds import train
    from benchmark.tests import tiny
    manifest = bench_run.read_json(bench_run.ROOT, "BENCHMARK.json")
    for cell in manifest["workloads"]:
        bench_run.load_cell(cell["name"])     # every file found by name
    cfg, traffic = tiny.gpt()
    traffic["chips"] = 1
    result = train.run({"name": "tiny_gpt", "chips": 1}, cfg, traffic, {},
                       {}, seed=2 ** 31 + 5, seconds=0.5, trace=False,
                       t_process=time.perf_counter(), require_chip=False)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"], list(line)
    assert list(line)[-1] == "compared"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    print("result line: the contract's keys, the compared numbers last")


if __name__ == "__main__":
    check_costs()
    check_trace()
    check_result_line()
    print("selfcheck passed")
