"""Profiling & performance accounting — the TPU-native replacement for the
reference's ad-hoc scoped timers (``Utils.timeIt`` at
``pipeline/api/net/TFNet.scala:176``, ``EstimateSupportive.throughputing*`` at
``pipeline/estimator/EstimateSupportive.scala``) and BigDL's per-phase
``metrics`` table (driven at ``Topology.scala:1184``).

Adds what the reference never had (SURVEY §5 "no sampling profiler, no trace
files"): ``jax.profiler`` trace capture and achieved-MFU accounting from XLA's
compiled cost analysis.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Dict, Optional

import jax

log = logging.getLogger("analytics_zoo_tpu.profiling")

#: Peak dense bf16 matmul FLOP/s per chip, keyed by the EXACT
#: ``jax.Device.device_kind`` string. Numbers: Google Cloud TPU
#: documentation, per chip (v4 275T, v5e 197T, v5p 459T, v6e 918T). Keys:
#: "TPU v5 lite" is what a v5e machine reports (chip run, PR 21); the
#: other three are the spellings jax's own table of TPU kinds uses
#: (its pallas ``tpu_info`` module, jax 0.9.0) and have not been
#: seen on a machine by this repo. A TPU kind that is not here is an
#: error (:func:`device_peak_flops`), not a default.
PEAK_FLOPS_BF16: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


def device_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-chip peak bf16 FLOP/s for MFU accounting. ``None`` only off-TPU
    (the CPU test mesh has no published peak); a TPU whose ``device_kind``
    is not in :data:`PEAK_FLOPS_BF16` raises, so that an MFU is never
    silently missing on a chip nobody entered."""
    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        return None
    try:
        return PEAK_FLOPS_BF16[d.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for TPU device_kind {d.device_kind!r}: add "
            f"it, with its source, to utils.profiling.PEAK_FLOPS_BF16 "
            f"(known: {sorted(PEAK_FLOPS_BF16)})") from None


def compiled_flops(compiled) -> Optional[float]:
    """Total FLOPs of one invocation of a compiled (lowered) jax function,
    from XLA's cost analysis. Returns None when the backend doesn't report."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend-dependent
        return None
    if ca is None:
        return None
    flops = ca.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


def jit_flops(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs for one call of ``jax.jit(fn)`` on these concrete args."""
    try:
        return compiled_flops(jax.jit(fn).lower(*args, **kwargs).compile())
    except Exception:  # pragma: no cover
        return None


def mfu(flops_per_sec: float, n_devices: Optional[int] = None) -> Optional[float]:
    """Achieved model-FLOPs-utilization given sustained FLOP/s across the
    mesh. None off-TPU; raises on a TPU kind without a published peak."""
    peak = device_peak_flops()
    if peak is None:
        return None
    n = n_devices if n_devices is not None else len(jax.devices())
    return flops_per_sec / (peak * n)


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """``jax.profiler`` trace capture scoped to a with-block; no-op when
    ``log_dir`` is None. View with TensorBoard's profile plugin / xprof."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", log_dir)


class Timer:
    """Scoped wall-clock timer with named laps — the ``timeIt`` role."""

    def __init__(self):
        self.laps: Dict[str, float] = {}

    @contextlib.contextmanager
    def lap(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.laps[name] = self.laps.get(name, 0.0) + time.perf_counter() - t0
