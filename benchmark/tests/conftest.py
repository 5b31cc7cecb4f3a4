"""The benchmark's own tests run on the CPU, on four virtual devices, so
that the tiny cells train on a ``data=4`` mesh as the four-chip cell does.
Run them with ``python -m pytest benchmark/tests -q`` from the repo's root;
they are not part of ``tests/``."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
