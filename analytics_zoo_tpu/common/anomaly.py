"""Anomaly sentinels — on-device divergence detection for the training
step (docs/guides/TRAINING.md "Anomaly detection & recovery").

The reference platform survived *executor* failures through Spark's
lineage recompute (``Topology.scala:1171-1253``); the loop itself had no
defense against the most common production failure: **numerical
divergence**. One poison batch or an fp-overflow NaNs the params and
every subsequent step silently trains garbage until a human reads the
loss curve. This module is the training-side sibling of serving's
poison-record isolation (``serving/server.py`` solo re-dispatch +
dead-letter): detect the bad step on device, contain it (skip the
update), and escalate to rollback when skipping is not enough.

Design constraints (all enforced here, consumed by
``pipeline/api/keras/training.py``):

* **Cheap and fused.** The checks are a handful of scalar ops folded
  into the already-compiled train step: non-finite loss, non-finite
  global gradient norm, and a relative spike of the gradient norm
  against its own EWMA baseline. They ride the step's XLA program — no
  extra dispatch, no extra host sync.
* **One packed scalar.** All flags come back as ONE int32 bitmask per
  step, read by the host alongside
  the loss it already reads back — see :data:`NAN_LOSS` /
  :data:`NAN_GRAD` / :data:`SPIKE` / :data:`GRAD_CLIPPED`.
* **Deterministic.** No RNG, no clock: the EWMA baseline is a pure
  function of the observed gradient norms (anomalous steps never teach
  it), so chaos tests reconcile the flagged-step set exactly against an
  injected ``train.grads`` fault plan, and ``zoo.train.sentinel=off``
  builds the exact step of a sentinel-free build (bit-identical
  numerics — the sentinel ops are gated at build time, not runtime).

Knobs (``docs/guides/CONFIG.md``): ``zoo.train.sentinel``
(``off|warn|recover``), ``zoo.train.spike_factor``,
``zoo.train.grad_clip``, ``zoo.train.max_skips_per_epoch``,
``zoo.train.max_rollbacks``.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp

from .context import get_zoo_context

__all__ = ["NAN_LOSS", "NAN_GRAD", "SPIKE", "ANOMALY_MASK", "GRAD_CLIPPED",
           "SentinelConfig", "resolve_config", "init_state", "check",
           "global_norm", "clip_by_global_norm", "inject_grads",
           "kinds_of", "FAULT_CODES", "EWMA_ALPHA", "WARMUP_STEPS",
           "EWMA_FLOOR"]

# -- the packed per-step flag word -------------------------------------------
#: loss came back non-finite (NaN/inf)
NAN_LOSS = 1
#: loss finite but the global gradient norm is non-finite
NAN_GRAD = 2
#: finite gradient norm spiked past spike_factor x its EWMA baseline
SPIKE = 4
#: any bit in here marks the step anomalous (recover mode discards it)
ANOMALY_MASK = NAN_LOSS | NAN_GRAD | SPIKE
#: informational: global-norm gradient clipping engaged this step
#: (zoo.train.grad_clip) — NOT an anomaly, never triggers a skip
GRAD_CLIPPED = 8

#: bit → metric label for zoo_train_anomaly_total{kind=}
KIND_BITS: Tuple[Tuple[int, str], ...] = (
    (NAN_LOSS, "nan_loss"), (NAN_GRAD, "nan_grad"), (SPIKE, "spike"))

#: ``train.grads`` fault-plan kind → the on-device poison code the host
#: feeds the step (0 = no fault); see :func:`inject_grads`
FAULT_CODES = {"nan_loss": 1, "nan_grad": 2, "spike": 3}

#: EWMA smoothing for the gradient-norm baseline: norm_t contributes
#: alpha, history (1-alpha). 0.1 tracks the slow decay of a healthy
#: norm while a 10x one-step spike still stands ~9x above the baseline.
EWMA_ALPHA = 0.1
#: observed (non-anomalous) steps before the spike check engages — the
#: first steps of a run legitimately swing the norm while the optimizer
#: finds scale, and an unprimed EWMA would flag them all
WARMUP_STEPS = 5
#: spike check additionally requires the baseline itself to stand above
#: this floor: a (near-)zero EWMA — fully-masked warmup window, frozen
#: phase, dead-ReLU start — makes the RELATIVE test meaningless (any
#: first real gradient would flag, recover mode would skip it, params
#: and baseline would never move, and the loop would livelock into
#: rollback escalation on a perfectly healthy run). Below the floor the
#: non-finite checks still guard; the spike check waits for a baseline.
EWMA_FLOOR = 1e-8


@dataclasses.dataclass(frozen=True)
class SentinelConfig:
    """Build-time resolution of the sentinel/clipping knobs — resolved
    ONCE per :class:`TrainingLoop` (like the fused-loss resolution) so
    every step builder of a loop agrees, and a ``sentinel=off`` loop
    builds steps with zero sentinel ops in them."""

    mode: str            # off | warn | recover
    spike_factor: float
    grad_clip: float     # 0 = off
    faults: bool         # step accepts per-step train.grads poison codes
    max_skips_per_epoch: int
    max_rollbacks: int

    @property
    def sentinel(self) -> bool:
        return self.mode != "off"

    @property
    def active(self) -> bool:
        """Whether the step builder must emit the extended signature
        (sentinel state carry and/or packed-flag output)."""
        return self.sentinel or self.grad_clip > 0


def resolve_config() -> SentinelConfig:
    """Read and validate the ``zoo.train.*`` sentinel knobs."""
    ctx = get_zoo_context()
    raw = ctx.get("zoo.train.sentinel", "off")
    mode = str(raw).strip().lower() if raw is not None else "off"
    from .context import FALSE_FLAG_SPELLINGS
    if mode in FALSE_FLAG_SPELLINGS:
        mode = "off"
    if mode not in ("off", "warn", "recover"):
        raise ValueError(f"zoo.train.sentinel must be off|warn|recover, "
                         f"got {raw!r}")
    spike_factor = float(ctx.get("zoo.train.spike_factor", 10.0))
    # the sentinel-only knobs are validated only when the sentinel is
    # on: a (mis-)configured value for a disabled feature must not
    # abort training that never reads it (grad_clip stands alone)
    if mode != "off" and spike_factor <= 1.0:
        raise ValueError(f"zoo.train.spike_factor must be > 1 "
                         f"({spike_factor})")
    grad_clip = float(ctx.get("zoo.train.grad_clip", 0.0) or 0.0)
    if grad_clip < 0:
        raise ValueError(f"zoo.train.grad_clip must be >= 0 ({grad_clip})")
    max_skips = int(ctx.get("zoo.train.max_skips_per_epoch", 8))
    if mode != "off" and max_skips < 0:
        raise ValueError(f"zoo.train.max_skips_per_epoch must be >= 0 "
                         f"({max_skips})")
    max_rollbacks = int(ctx.get("zoo.train.max_rollbacks", 3))
    if mode != "off" and max_rollbacks < 1:
        raise ValueError(f"zoo.train.max_rollbacks must be >= 1 "
                         f"({max_rollbacks})")
    faults = bool(ctx.get("zoo.faults.enabled", False))
    return SentinelConfig(mode=mode, spike_factor=spike_factor,
                          grad_clip=grad_clip,
                          faults=faults and mode != "off",
                          max_skips_per_epoch=max_skips,
                          max_rollbacks=max_rollbacks)


# ---------------------------------------------------------------------------
# on-device pieces (called from inside the jitted step)
# ---------------------------------------------------------------------------

def init_state() -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fresh EWMA carry ``(baseline_norm, observed_count)`` — two f32
    scalars threaded through the step like the rest of its state."""
    return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))


def global_norm(grads) -> jnp.ndarray:
    """Global L2 norm of a gradient tree, accumulated in f32 regardless
    of the compute dtype (a bf16 partial sum would overflow exactly on
    the exploding gradients this exists to catch)."""
    leaves = jax.tree_util.tree_leaves(grads)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    return jnp.sqrt(sq)


def check(loss, gnorm, state, spike_factor: float):
    """Classify one step: returns ``(flags, new_state)``.

    The three kinds are mutually exclusive by construction (checked in
    severity order), so the host's per-kind counters partition the
    anomalies exactly. An anomalous step never updates the EWMA baseline
    — a spike folded into its own baseline would mask the next one."""
    ewma, count = state
    loss32 = loss.astype(jnp.float32)
    nan_loss = ~jnp.isfinite(loss32)
    nan_grad = jnp.isfinite(loss32) & ~jnp.isfinite(gnorm)
    warmed = (count >= WARMUP_STEPS) & (ewma >= EWMA_FLOOR)
    spike = (jnp.isfinite(loss32) & jnp.isfinite(gnorm) & warmed
             & (gnorm > spike_factor * ewma))
    flags = (jnp.where(nan_loss, NAN_LOSS, 0)
             | jnp.where(nan_grad, NAN_GRAD, 0)
             | jnp.where(spike, SPIKE, 0)).astype(jnp.int32)
    anomalous = flags > 0
    seeded = jnp.where(count > 0,
                       (1.0 - EWMA_ALPHA) * ewma + EWMA_ALPHA * gnorm,
                       gnorm)
    new_ewma = jnp.where(anomalous, ewma, seeded)
    new_count = jnp.where(anomalous, count, count + 1.0)
    return flags, (new_ewma, new_count)


def clip_by_global_norm(grads, gnorm, clip: float):
    """Scale the tree so its global norm is at most ``clip``; returns
    ``(clipped_grads, engaged)``. A NON-FINITE norm leaves the grads
    untouched and ``engaged`` false: ``clip/inf`` is 0, and silently
    zeroing every (finite) leaf would turn an overflowing step into an
    undetected no-op update — the divergence must stay visible (and,
    with the sentinels on, flagged) rather than be masked by the
    clipper."""
    finite = jnp.isfinite(gnorm)
    scale = jnp.where(finite,
                      jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-16)),
                      1.0)
    engaged = finite & (gnorm > clip)
    clipped = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    return clipped, engaged


def inject_grads(loss, grads, code, scale):
    """Apply a ``train.grads`` fault-plan entry on device (chaos only —
    compiled into the step only when ``zoo.faults.enabled`` was set at
    build time). ``code`` follows :data:`FAULT_CODES`; ``scale`` is the
    spike multiplier. ``code == 0`` is an exact no-op on the values."""
    nan = jnp.asarray(jnp.nan, jnp.float32)
    loss = jnp.where(code == FAULT_CODES["nan_loss"],
                     jnp.asarray(jnp.nan, loss.dtype), loss)

    def poison(g):
        f = jnp.where(code == FAULT_CODES["nan_grad"], nan, 1.0)
        f = jnp.where(code == FAULT_CODES["spike"], scale, f)
        return g * f.astype(g.dtype)

    return loss, jax.tree.map(poison, grads)


# ---------------------------------------------------------------------------
# host-side decode
# ---------------------------------------------------------------------------

def kinds_of(flags: int) -> List[str]:
    """Metric labels for a packed flag word (empty when healthy)."""
    return [kind for bit, kind in KIND_BITS if flags & bit]
