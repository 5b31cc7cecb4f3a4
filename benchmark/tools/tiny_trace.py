"""Record the small trace that ``selfcheck.py`` reduces: a few dependent
1024 x 1024 bf16 matrix products with idle gaps of known length between
them. Run on the chip; writes ``<out>/tiny.xplane.pb`` and what was done."""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    f = jax.jit(lambda a: (a @ a) * jnp.bfloat16(1e-3))
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    f(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    t0 = time.perf_counter()
    calls = 0
    for _ in range(4):
        for _ in range(5):
            a = f(a)
            calls += 1
        a.block_until_ready()
        time.sleep(0.02)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "tiny.xplane.pb"))
    with open(os.path.join(out, "tiny.json"), "w") as fh:
        json.dump({"calls": calls, "sleeps": 4, "sleep_s": 0.02,
                   "wall_s": wall, "device": jax.devices()[0].device_kind,
                   "bytes": os.path.getsize(src)}, fh)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
