"""Training engine — the TPU-native replacement for the reference's
``InternalDistriOptimizer`` (``Topology.scala:1062-1540``) and the
``compile/fit/evaluate/predict`` facade (``Topology.scala:135,343,418,496``).

Architecture (vs the reference's per-iteration Spark jobs + BlockManager
parameter-server allreduce, ``wp-bigdl.md:113-160``):

* ONE jitted ``train_step`` — forward, backward, optimizer update — traced
  once, compiled by XLA, and run per minibatch with donated buffers.
* Data parallelism = batch sharded over the mesh ``data`` axis
  (``NamedSharding``); params replicated. XLA GSPMD inserts the gradient
  psum over ICI — there is no separate communication runtime to operate.
* Input batches stream through ``FeatureSet`` with a background assembly
  thread + double-buffered ``device_put`` so the chip never waits on the host.
* Failure handling keeps the reference's semantics
  (``Topology.scala:1171-1253``): on a step failure, reload the latest
  checkpoint and retry, bounded by ``zoo.failure.retry_times`` within
  ``zoo.failure.retry_window_sec``; checkpoints are cut on the
  ``set_checkpoint`` trigger (``Topology.scala:245-255,1161-1168``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ....common import anomaly, faults
from ....common.context import get_zoo_context
from ....common.reliability import RetryBudget
from ....common.triggers import (EveryEpoch, MaxEpoch, SeveralIteration,
                                 TrainLoopState, Trigger)
from ....feature.feature_set import FeatureSet, prefetch_to_device
from ....observability import (default_registry, instrument_jit, span,
                                step_ledger)
from ....observability.compile import xla_compile_totals
from ....observability.goodput import (GoodputLedger, InflightProbe,
                                       goodput_enabled)
from ....observability.tracing import HostPhase, trace_annotation
from ....parallel import mesh as mesh_lib
from ....utils.checkpoint import CheckpointManager
from . import metrics as metrics_lib
from . import objectives, optimizers as optim_lib
from .engine import KerasNet

log = logging.getLogger("analytics_zoo_tpu.training")


class TrainingPreempted(SystemExit):
    """Raised out of ``fit`` after a SIGTERM-requested final checkpoint
    (``zoo.checkpoint.on_sigterm``): the snapshot is on disk, in-memory
    model state is published, and the process should now exit — a
    ``SystemExit`` subclass so it escapes the step-failure retry loop and
    terminates cleanly (the TPU-preemption analogue of the reference's
    driver-failure snapshot)."""


class TrainingDiverged(RuntimeError):
    """The anomaly sentinels (``zoo.train.sentinel=recover``) could not
    contain a divergence: either skip-then-rollback recovery exhausted
    its ``zoo.train.max_rollbacks`` budget, or escalation was required
    with no checkpoint to roll back to. Raised INSTEAD of looping
    forever or silently training on garbage — the params published on
    the model are the last known-good (restored) state."""


class _RollbackRequested(RuntimeError):
    """Internal escalation signal: more than
    ``zoo.train.max_skips_per_epoch`` updates were discarded in one
    epoch — reload the last good checkpoint and replay with the
    offending data window skipped. Handled by ``_fit_with_retry``
    under the rollback :class:`RetryBudget`; never escapes ``fit``."""

    def __init__(self, skips: int, epoch: int):
        super().__init__(
            f"{skips} anomalous step(s) skipped in epoch {epoch} "
            f"(zoo.train.max_skips_per_epoch exceeded)")
        self.skips = skips
        self.epoch = epoch


#: shape of the "no fault" train.grads input — a module constant so the
#: hot loop hands the SAME host array to every healthy dispatch instead
#: of allocating one per step
_NO_FAULT = np.zeros(2, np.float32)


class CompiledSpec:
    def __init__(self, optimizer, loss, metrics):
        self.optimizer = optimizer
        self.loss = loss
        self.metrics = metrics


# ---------------------------------------------------------------------------
# data iteration helpers
# ---------------------------------------------------------------------------

def _as_list(x):
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _num_examples(x) -> int:
    return _as_list(x)[0].shape[0]


def _take(x, idx):
    xs = [np.asarray(a)[idx] for a in _as_list(x)]
    return xs if len(xs) > 1 else xs[0]


def _host_once(x):
    """Materialize device-resident arrays on host ONCE before a batch loop —
    ``_take``'s per-batch ``np.asarray`` would otherwise re-read the whole
    array from HBM every batch (FeatureSet keeps ``jax.Array`` features
    device-resident for the extract→fit chain)."""
    if x is None:
        return None
    xs = [np.asarray(a) if isinstance(a, jax.Array) else a
          for a in _as_list(x)]
    return xs if len(xs) > 1 else xs[0]


def iter_batches(x, y, batch_size: int, *, shuffle: bool, seed: int,
                 drop_last: bool):
    """Host-side minibatch iterator over numpy arrays (evaluate/predict path;
    training streams through ``FeatureSet`` instead)."""
    x, y = _host_once(x), _host_once(y)
    n = _num_examples(x)
    order = np.arange(n)
    if shuffle:
        np.random.default_rng(seed).shuffle(order)
    end = n - (n % batch_size) if drop_last else n
    for i in range(0, end, batch_size):
        idx = order[i:i + batch_size]
        yield _take(x, idx), (None if y is None else _take(y, idx))


def _pad_to(x, size: int):
    """Pad the batch dim to ``size`` by repeating the last row."""
    out = []
    for a in _as_list(x):
        a = np.asarray(a)
        pad = size - a.shape[0]
        if pad > 0:
            a = np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)
        out.append(a)
    return out if len(out) > 1 else out[0]


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def _aux_loss_sum(state):
    """Sum of every ``aux_loss`` leaf a layer left in the network state
    (e.g. ``SparseMoE``'s load-balance loss). A trace-time pytree walk —
    models without aux losses pay nothing. Returns None when absent."""
    total = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if path and getattr(path[-1], "key", None) == "aux_loss":
            total = leaf if total is None else total + leaf
    return total


def _decoder_mixers(model) -> Dict[str, int]:
    """Blocks by the kind of their mixer over the ``DecoderStack`` layers a
    container holds (``DecoderStack.mixers``); empty where it holds none."""
    out: Dict[str, int] = {}
    for layer in getattr(model, "layers", None) or ():
        for kind, n in (getattr(layer, "mixers", None) or {}).items():
            out[kind] = out.get(kind, 0) + n
    return out


class _FullPassEveryEpoch(Trigger):
    """``EveryEpoch`` over a sliced dataset: fires only when the finished
    slice pass completes a FULL pass over all slices
    (``ZooTrigger.scala:53-58``: ``currentSlice % numSlice == 0``)."""

    def __init__(self, num_slices: int):
        self.num_slices = int(num_slices)

    def __call__(self, state: TrainLoopState) -> bool:
        return state.epoch_finished and state.epoch % self.num_slices == 0


def _slice_aware(trig: Optional[Trigger],
                 n_slices: int) -> Optional[Trigger]:
    """``trig`` as the loop has to see it under a sliced disk set, where one
    loop "epoch" is ONE slice pass: epoch triggers count FULL passes."""
    if isinstance(trig, EveryEpoch):
        return _FullPassEveryEpoch(n_slices)
    if isinstance(trig, MaxEpoch):
        return MaxEpoch(trig.max_epoch * n_slices)
    if trig is not None and not isinstance(
            trig, (SeveralIteration, _FullPassEveryEpoch)):
        log.warning("trigger %s under a %d-slice DiskFeatureSet observes "
                    "SLICE passes as epochs, not full passes",
                    type(trig).__name__, n_slices)
    return trig


def _fired_within(trigger: Trigger, state: TrainLoopState,
                  prev_iter: int) -> bool:
    """Whether a trigger fired at any step in ``(prev_iter, state.iteration]``:
    for a caller that looks only at epoch boundaries (the histogram writer
    below), so that an interval trigger's fire inside the epoch is acted on
    at the boundary and not lost."""
    if isinstance(trigger, SeveralIteration):
        return state.iteration // trigger.interval > prev_iter // trigger.interval
    return trigger(state)


def _write_param_histograms(tb, params, epoch: int, iteration: int,
                            n_steps: int = 0) -> None:
    """Per-layer weight histograms when the TrainSummary's "Parameters"
    trigger fires for ``epoch`` (reference:
    ``TrainSummary.setSummaryTrigger("Parameters", ...)`` +
    ``Summary.scala``'s histogram writer). Called at the epoch's boundary
    ``iteration``, where the params are host-visible; an iteration-based
    trigger is checked over the epoch's whole ``(iteration - n_steps,
    iteration]`` window (without ``n_steps``: the boundary iteration
    itself)."""
    trig = getattr(tb, "parameters_trigger", None)
    if trig is not None:
        state = TrainLoopState(iteration=iteration, epoch=epoch,
                               epoch_finished=True)
        if not _fired_within(trig, state, iteration - max(n_steps, 1)):
            return
    else:
        freq = getattr(tb, "parameters_every_epochs", None)
        if not freq or epoch % freq:
            return
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        tb.add_histogram(f"Parameters/{name}", np.asarray(leaf), iteration)


@jax.jit
def _copy_leaves(leaves):
    return [jnp.copy(a) for a in leaves]


def _clone_tree(tree):
    """Fresh buffers for every array leaf. The donated train step deletes its
    input buffers, so a tree that has to outlive a step must never alias one
    that enters the step. ``fit`` itself no longer keeps such a tree: the
    model's trees are handed to the loop (``_open_fit``), which holds the
    only copy while steps run and hands it back when it ends
    (``_publish``). Who still clones, and only with a checkpoint
    directory configured: ``_close_epoch``, at the end of an epoch that
    another follows, the boundary state the retry loop falls back to when
    the newest snapshot is older or torn (no step is in flight then; the
    copy of the boundary before is dropped first, so two sets at the most);
    and ``_segment_begin``, the boundary state the SIGTERM grace budget cuts
    a mid-epoch snapshot from, while a segment is estimated to outlast the
    budget.

    All device leaves are copied in ONE jitted dispatch: a per-leaf
    ``jnp.copy`` costs a separate ``jit(copy)`` trace and compile per leaf
    the first time a tree arrives with new shardings, and a dispatch per
    leaf every time."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dev_idx = [i for i, a in enumerate(leaves) if isinstance(a, jax.Array)]
    if dev_idx:
        copies = _copy_leaves([leaves[i] for i in dev_idx])
        for i, c in zip(dev_idx, copies):
            leaves[i] = c
    leaves = [np.copy(a) if isinstance(a, np.ndarray) else a for a in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _any_deleted(tree) -> bool:
    """Whether a device leaf of ``tree`` has been given up to a step."""
    return any(isinstance(a, jax.Array) and a.is_deleted()
               for a in jax.tree_util.tree_leaves(tree))


def _usable(tree) -> bool:
    """Whether every device leaf of ``tree`` can still be read: none given
    up to a step, and none the output of a step that failed on the device
    (such an array is not deleted; it raises when it is waited for)."""
    if _any_deleted(tree):
        return False
    try:
        jax.block_until_ready(tree)
    except Exception:  # zoolint: disable=ZL007 whatever the runtime raises
        return False
    return True


def _buffers(a) -> Tuple:
    """The device buffers behind an array, as addresses."""
    try:
        return tuple(s.data.unsafe_buffer_pointer()
                     for s in a.addressable_shards)
    except Exception:  # zoolint: disable=ZL007 a backend without pointers
        return (id(a),)


def _unshared(tree):
    """``tree`` with every device leaf on buffers of its own: a leaf that
    stands on the buffers of an earlier one (a layer's counters that start
    from one zero) is copied, for a step cannot be given one buffer
    twice."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    seen = set()
    for i, a in enumerate(leaves):
        if isinstance(a, jax.Array) and not a.is_deleted():
            held = _buffers(a)
            if seen.intersection(held):
                leaves[i] = jnp.copy(a)
            else:
                seen.update(held)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _tree_bytes(tree) -> int:
    return sum(int(getattr(a, "nbytes", 0) or 0)
               for a in jax.tree_util.tree_leaves(tree))


class _SentinelMonitor:
    """The host side of the guarded step (``common/anomaly.py``): it holds
    what that step takes and returns beyond the plain one (the EWMA carry
    and the fault code in; a packed int32 flag word out), so that the loop
    dispatches one step one way (:meth:`step_args`, :meth:`took`), and it
    keeps the books on the flags.

    Flag readbacks trail the dispatch stream by a small lag window so
    observing them never syncs the pipeline the way an eager per-step
    read would — the device-side skip already happened inside the step;
    the host only needs the flags for metrics, the per-epoch skip
    budget, and the rollback replay set, all of which tolerate a
    few-dispatch delay. Everything here is deterministic: the chaos
    harness reconciles the counters exactly against an injected
    ``train.grads`` plan."""

    #: dispatches a flag word may trail the stream before being read
    LAG = 4

    def __init__(self, loop: "TrainingLoop", cfg: anomaly.SentinelConfig):
        self.loop = loop
        self.cfg = cfg
        # EWMA carry (device scalars) — fresh per fit attempt, as the
        # monitor is: after a rollback the restored params' gradient
        # scale is the baseline worth learning, not the diverging run's
        self.sstate = anomaly.init_state()
        self.pending: collections.deque = collections.deque()
        self.epoch = 0
        self.epoch_start = 0                # iteration at epoch start
        self.epoch_skips = 0
        self.epoch_flags: List[int] = []    # one per recorded loss
        self.epoch_step_iters: List[int] = []   # global iter per loss

    def begin_epoch(self, epoch: int, start_iter: int) -> None:
        self.drain()                        # belongs to the PREVIOUS epoch
        self.epoch = epoch
        self.epoch_start = start_iter
        self.epoch_skips = 0
        self.epoch_flags = []
        self.epoch_step_iters = []

    def step_key(self, it: int):
        """Replay-stable identity of a dispatched step: (epoch, ordinal
        within the epoch). Global iteration numbers shift when a
        mid-epoch snapshot restores (the epoch re-streams from batch 0
        while the iteration counter resumes mid-epoch), but the batch
        order per epoch is deterministic — the ordinal is what maps
        back to the same data window on replay."""
        return (self.epoch, it - self.epoch_start)

    def flagged(self, it: int) -> bool:
        """Whether step ``it`` was flagged before a rollback: its data
        window is not dispatched again on the replay."""
        return self.step_key(it) in self.loop._anomalous_steps

    def step_args(self, rng, x, y) -> Tuple:
        """What the guarded step takes after the three state trees."""
        return (self.sstate, rng, self._fault_input(), x, y)

    def took(self, it: int, out) -> Tuple:
        """Keep what the guarded step returned beyond the plain step's
        ``(params, opt_state, net_state, loss)``; hand those four back."""
        params, opt_state, net_state, self.sstate, loss, flags = out
        self.epoch_step_iters.append(it)
        self.pending.append((it, flags))
        if len(self.pending) > self.LAG:
            self._drain_one()
        return params, opt_state, net_state, loss

    def _fault_input(self) -> np.ndarray:
        """Host-side ``train.grads`` fault scheduling: one site call per
        dispatched optimizer step. Returns the ``[code, scale]`` pair the
        compiled step consumes (``anomaly.inject_grads``) — zeros (the
        shared no-fault constant) unless an active plan fires a
        nan_loss/nan_grad/spike spec at this call index."""
        spec = faults.inject("train.grads") if self.cfg.faults else None
        if spec is None:
            return _NO_FAULT
        code = anomaly.FAULT_CODES.get(spec.kind)
        if code is None:        # e.g. a latency spec: already applied
            return _NO_FAULT
        return np.asarray([code, spec.scale], np.float32)

    def note_replay_skip(self) -> None:
        """A step of a rollback replay was not re-dispatched (the
        offending data window) — counted as skipped, no loss recorded."""
        self.loop._m_skipped.inc()

    def drain(self) -> None:
        while self.pending:
            self._drain_one()

    def _drain_one(self) -> None:
        it, flags_dev = self.pending.popleft()
        f = int(np.asarray(flags_dev))
        self.epoch_flags.append(f)
        if f & anomaly.GRAD_CLIPPED:
            self.loop._m_clip.inc()
        kinds = anomaly.kinds_of(f)
        if not kinds:
            return
        for kind in kinds:
            self.loop._m_anomaly[kind].inc()
        self.loop._registry.emit(
            "train.anomaly", iteration=it, epoch=self.epoch,
            kinds=",".join(kinds), mode=self.cfg.mode,
            action="skip" if self.cfg.mode == "recover" else "warn")
        if self.cfg.mode != "recover":
            log.warning(
                "anomalous step at iteration %d (%s) — "
                "zoo.train.sentinel=warn: update APPLIED", it,
                ",".join(kinds))
            return
        self.loop._m_skipped.inc()
        self.loop._anomalous_steps.add(self.step_key(it))
        self.epoch_skips += 1
        log.warning(
            "anomalous step at iteration %d (%s): update "
            "discarded (%d/%d skips this epoch)", it,
            ",".join(kinds), self.epoch_skips,
            self.cfg.max_skips_per_epoch)
        if self.epoch_skips > self.cfg.max_skips_per_epoch:
            raise _RollbackRequested(self.epoch_skips, self.epoch)

    def loss_mask(self, n: int) -> np.ndarray:
        """Valid-loss mask over this epoch's ``n`` recorded losses: in
        recover mode an anomalous step's loss was never applied, so it
        is excluded from the epoch mean (matching a run that never saw
        the poison batch)."""
        self.drain()
        mask = np.ones(n, bool)
        if self.cfg.mode == "recover":
            for i, f in enumerate(self.epoch_flags[:n]):
                if f & anomaly.ANOMALY_MASK:
                    mask[i] = False
        return mask


@dataclasses.dataclass
class _FitRun:
    """What one attempt of a fit carries from ``TrainingLoop._open_fit``
    through its epochs: the live trees that the donated step consumes and
    returns (the ONLY copy of weights, layer state and optimizer state on
    the device while steps run; the model's own were handed over), and the
    loop's bookkeeping."""

    fs: FeatureSet
    batch_size: int             # rounded up to the data-parallel size
    params: Any
    opt_state: Any
    net_state: Any
    base_rng: Any               # step i's key is fold_in(base_rng, i)
    mgr: Optional[CheckpointManager]
    ckpt_trigger: Trigger
    end_trigger: Optional[Trigger]
    target_epoch: int
    loop_state: TrainLoopState
    monitor: Optional[_SentinelMonitor]     # None: the plain step
    history: Dict[str, List[float]] = dataclasses.field(
        default_factory=lambda: {"loss": []})
    stop: bool = False          # the end trigger fired inside an epoch
    epoch_t0: float = 0.0       # the open epoch's start (``time.time()``)


def _host_losses(losses) -> np.ndarray:
    """An epoch's per-step losses, read back to one host vector."""
    if not losses:
        return np.zeros(0, np.float32)
    return np.concatenate([np.atleast_1d(np.asarray(l)) for l in losses])


# ---------------------------------------------------------------------------
# The training loop (InternalDistriOptimizer / LocalOptimizer unified)
# ---------------------------------------------------------------------------

#: where the HOST spends a fit, in the order a fit goes through them: the
#: ``phase`` values of ``zoo_train_host_seconds_total`` and the keys of
#: ``model.last_fit_report["host_s"]``. Each is also an event ``train.<phase>``
#: of the profiler's host plane.
HOST_PHASES = ("fit.enter", "data.pull", "data.put", "step.dispatch",
               "epoch.tail", "epoch.publish")


class _HostPhases(contextlib.ExitStack):
    """The host side of a fit by phase (:data:`HOST_PHASES`).

    The three that run every step (``pull`` and ``put``, entered by
    ``prefetch_to_device``, and ``dispatch``) are :class:`HostPhase`
    objects: one counter add and one profiler annotation. The three that
    run once a fit or once an epoch follow one another (``_open_fit``,
    ``_close_epoch``):
    :meth:`switch` ends the open one and begins the next as a ``span``
    (histogram, event, annotation) whose seconds the counter gets too. The
    stack is entered around each fit attempt, so that an exception ends
    whatever phase was open."""

    def __init__(self, registry):
        super().__init__()
        self._registry = registry
        # phase iterates HOST_PHASES — a 6-entry module constant
        self.counters = {
            phase: registry.counter(  # zoolint: disable=ZL015 bounded label set
                "zoo_train_host_seconds_total",
                "wall seconds the training loop's host thread spent in "
                "each phase of fit (where the host waits; whether the "
                "chip starves is zoo_badput_seconds_total{category="
                "data_wait})", labels={"phase": phase})
            for phase in HOST_PHASES}
        self.pull = HostPhase("train.data.pull", self.counters["data.pull"])
        self.put = HostPhase("train.data.put", self.counters["data.put"])
        self.dispatch = HostPhase("train.step.dispatch",
                                  self.counters["step.dispatch"])

    def switch(self, phase: Optional[str] = None) -> None:
        """End the open per-fit/per-epoch phase; begin ``phase`` if given."""
        self.close()
        if phase is not None:
            t0 = time.perf_counter()
            self.callback(lambda: self.counters[phase].inc(
                time.perf_counter() - t0))
            self.enter_context(span("train." + phase,
                                    registry=self._registry))

    def seconds(self) -> Dict[str, float]:
        return {phase: c.value for phase, c in self.counters.items()}


class TrainingLoop:
    """Owns the jitted step functions for one (model, optimizer, loss) triple."""

    def __init__(self, model: KerasNet, optimizer: optax.GradientTransformation,
                 loss: Callable, metrics: Sequence[metrics_lib.Metric] = ()):
        self.model = model
        self.optimizer = optimizer
        self.loss = loss
        self.metrics = list(metrics)
        self.mesh = mesh_lib.global_mesh()
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        # observability (docs/guides/OBSERVABILITY.md): every fit updates
        # the zoo_train_* family in the process-wide registry
        self._registry = default_registry()
        self._m_step_time = self._registry.histogram(
            "zoo_train_step_seconds",
            "optimizer-step wall time (an epoch's wall time over its steps)")
        self._m_throughput = self._registry.gauge(
            "zoo_train_records_per_sec", "training examples/sec, last epoch")
        self._m_mfu = self._registry.gauge(
            "zoo_train_mfu",
            "achieved model-FLOPs utilization, last epoch "
            "(zoo.metrics.flops + a known chip peak)")
        self._m_steps = self._registry.counter(
            "zoo_train_steps_total", "optimizer steps run")
        self._m_examples = self._registry.counter(
            "zoo_train_examples_total", "training examples consumed")
        # evaluate/predict get the same treatment fit got (ROADMAP
        # eval/predict instrumentation pass): weighted step-time
        # histograms + record counters, spans around the whole pass
        self._m_eval_step_time = self._registry.histogram(
            "zoo_eval_step_seconds",
            "evaluate step wall time (amortized over the streamed batches)")
        self._m_eval_records = self._registry.counter(
            "zoo_eval_examples_total", "examples evaluated (pad rows excluded)")
        self._m_predict_step_time = self._registry.histogram(
            "zoo_predict_step_seconds",
            "predict step wall time (amortized over the streamed batches)")
        self._m_predict_records = self._registry.counter(
            "zoo_predict_examples_total", "examples predicted")
        self._flops_per_example: Optional[float] = None
        # durable checkpointing (docs/guides/TRAINING.md): the manager of
        # the fit attempt in flight (its async writer is joined/closed by
        # _close_active_ckpt_mgr) and the SIGTERM preemption latch
        self._active_ckpt_mgr: Optional[CheckpointManager] = None
        self._preempted = threading.Event()
        # SIGTERM grace budget (zoo.checkpoint.sigterm_grace_s): the
        # in-flight dispatch segment's start stamp + EWMA duration
        # estimate, and — only when the estimate already exceeds the
        # budget — a cloned copy of the last boundary state the handler
        # can cut a MID-EPOCH snapshot from (the in-flight trees are
        # donated to the dispatch and unreadable by then)
        self._sigterm_grace: Optional[float] = None
        self._segment_t0: Optional[float] = None
        self._segment_est: Optional[float] = None
        self._segment_count = 0     # loop-lifetime; first sample discarded
        self._boundary_ref = None
        self._apply_loss = None     # resolved once per loop (fused CE)
        # the params' sharding tree when any leaf is sharded (set by fit
        # before the step first traces; _pin_params), else None
        self._param_shardings = None
        self._opt_state_shardings = None
        # anomaly sentinels (docs/guides/TRAINING.md "Anomaly detection
        # & recovery"): config resolved once per loop like _apply_loss;
        # the per-fit recovery state (flagged iterations, rollback
        # budget) is (re)initialized at each fit() entry
        self._sentinel: Optional[anomaly.SentinelConfig] = None
        # kind iterates anomaly.KIND_BITS — a 3-entry module constant
        # (nan_loss/nan_grad/spike), bounded just like a literal
        self._m_anomaly = {
            kind: self._registry.counter(  # zoolint: disable=ZL015 bounded label set
                "zoo_train_anomaly_total",
                "anomalous training steps detected by the sentinels, by "
                "kind (zoo.train.sentinel)", labels={"kind": kind})
            for _bit, kind in anomaly.KIND_BITS}
        self._m_skipped = self._registry.counter(
            "zoo_train_skipped_steps_total",
            "optimizer steps whose update was discarded (sentinel skip) "
            "or not re-dispatched on rollback replay")
        self._m_rollback = self._registry.counter(
            "zoo_train_rollback_total",
            "skip-budget escalations that reloaded the last good "
            "checkpoint and replayed past the offending window")
        self._m_clip = self._registry.counter(
            "zoo_train_grad_clip_engaged_total",
            "steps where zoo.train.grad_clip global-norm clipping "
            "actually rescaled the gradients")
        self._anomalous_steps: set = set()   # {(epoch, ordinal)} flagged
        self._rollback_budget: Optional[RetryBudget] = None
        self._rollback_pending = False
        # goodput/badput attribution (docs/guides/OBSERVABILITY.md
        # "Goodput & performance attribution"): one ledger per fit,
        # created at fit_feature_set entry when zoo.goodput.enabled
        self._goodput = None
        self._gp_restarting = False   # a retry attempt's resume pending
        # what the device still has to run (one probe per fit) and where
        # the host spends the fit: with the ledger, the three views that
        # model.last_fit_report hands out
        self._probe: Optional[InflightProbe] = None
        self._phases = _HostPhases(self._registry)
        # last_fit_report["state"]
        self._state_report: Dict[str, Any] = {}
        # last_fit_report["remat_saved_bytes"], set where the step is traced
        self._remat_saved: Dict[str, int] = {}
        # last_fit_report["step_census"], taken where zoo.metrics.flops
        # compiles the step for its cost analysis
        self._step_census: Optional[Dict[str, Any]] = None

    # -- goodput attribution -------------------------------------------------
    def _gp_note(self, category: str) -> None:
        """Attribute wall clock since the ledger's mark to ``category``
        (no-op outside an accounted fit)."""
        if self._goodput is not None:
            self._goodput.note(category)

    def _dispatch(self, fn, iteration: int, *args):
        """Dispatch optimizer step ``iteration``: the in-flight probe sees
        the depth it finds and the loss the step will fill, the profiler a
        ``zoo.train.step`` step event."""
        probe = self._probe
        probe.at_dispatch()
        with self._phases.dispatch, jax.profiler.StepTraceAnnotation(
                "zoo.train.step", step_num=iteration):
            out = fn(*args)
        # the step returns (params, opt_state, net_state, loss) or, under
        # the sentinels, (..., sentinel state, loss, flags)
        probe.dispatched(out[-1] if len(out) == 4 else out[-2], 1)
        return out

    def _drain(self, losses, reduce: bool = True):
        """Wait for the device to finish what the epoch dispatched, before
        the epoch's tail reads its losses back; returns their mean as a
        device scalar (``None`` with ``reduce=False`` or no losses). The
        mean is dispatched first, behind the steps still in flight, so
        that its host work (a compilation, whenever the epoch's length is
        new) hides under them. All of this wait is the device running
        steps (the host was ahead by so much), so the ledger books it as
        ``device_step`` and no host phase covers it."""
        if not losses:
            return None
        busy = self._probe.busy()
        with trace_annotation("train.epoch.drain"):
            mean = (jnp.mean(jnp.concatenate(
                [jnp.atleast_1d(l) for l in losses])) if reduce else None)
            jax.block_until_ready(losses[-1])
        if busy:
            self._gp_note("device_step")
        return mean

    def _fit_report(self, t_open: float, t_end: float,
                    host_before: Dict[str, float],
                    compile_before: Dict[str, Dict[str, float]]
                    ) -> Dict[str, Any]:
        """``model.last_fit_report``: what the registry's counters hold,
        as deltas over the fit that just ended."""
        compiled: Dict[str, Dict[str, float]] = {}
        for fn, now in xla_compile_totals().items():
            before = compile_before.get(fn, {})
            delta = {k: v - before.get(k, 0.0) for k, v in now.items()}
            if any(delta.values()):
                compiled[fn] = delta
        report = {
            "wall_s": t_end - t_open,
            "steps": self._probe.steps,
            "ledger": (self._goodput.seconds()
                       if self._goodput is not None else {}),
            "host_s": {phase: v - host_before[phase]
                       for phase, v in self._phases.seconds().items()},
            "inflight": self._probe.summary(),
            "compile": compiled,
            # the one copy of weights, layer state and optimizer state the
            # loop held: its bytes, how it came (``handed_over``: the
            # model's own buffers; ``placed``: copied onto the mesh, from
            # the host or another sharding; ``restored``: a checkpoint's)
            # and how it went back (``handed_back``, or ``cloned``: the
            # retry loop's boundary copy, only with a checkpoint directory)
            "state": dict(self._state_report),
            # what the compiled step keeps for its backward pass beside the
            # inputs of a rematerialised DecoderStack's blocks, by kind
            # (zoo_remat_saved_bytes{what=}): the flash kernels' outputs
            # and row statistics; zeros for any other model
            "remat_saved_bytes": dict(self._remat_saved),
            # the model's DecoderStack blocks by the kind of their mixer
            # (zoo_decoder_blocks{mixer=}); empty for any other model
            "mixers": _decoder_mixers(self.model),
        }
        if self._step_census is not None:
            # the compiled step's instructions by device scope and pass
            # (observability/step_ledger.py::census); only with
            # zoo.metrics.flops on, whose compilation it is read from
            report["step_census"] = self._step_census
        return report

    # -- jitted steps -------------------------------------------------------
    #: the labels of the most recent fused-CE gauge write in this process —
    #: a later non-fused (or differently-headed) loop zeroes the stale
    #: series so the scrape never claims fusion is active when it is not
    _last_fused_labels = None
    _FUSED_GAUGE_HELP = ("1 while the fused blockwise LM-head cross-entropy "
                         "is active for the current training loop")

    def _loss_application(self):
        """``fn(params, net_state, x, y, rng) -> (loss, new_state)`` — the
        train step's forward+loss. Resolves the fused LM-head
        cross-entropy (``fused_loss.resolve_fused_loss``,
        ``zoo.train.fused_ce``) ONCE per loop — re-resolving would re-log
        and re-write the gauge: a big-vocab Dense head with
        a sparse-CE loss streams through ``ops/fused_cross_entropy`` so the
        ``(B·T, V)`` logits tensor never materializes; everything else runs
        the plain apply + objective (the oracle path, which ``evaluate``
        always uses)."""
        if self._apply_loss is not None:
            return self._apply_loss
        model, loss_fn = self.model, self.loss
        from .fused_loss import resolve_fused_loss
        from .seq_pipe import (pipe_intercept, resolve_pipe_spec,
                               resolve_seq_attention, seq_attention_scope)
        from .sharded_embed import resolve_sharded_embeddings
        # sequence/pipeline step integration (zoo.train.seq_attention /
        # zoo.train.pipe_stages): resolved once per loop like the fused
        # loss, applied as trace-time scopes around the step's forward
        # so existing models ride seq/pipe meshes unchanged
        seq_mode = resolve_seq_attention()
        pipe_spec = resolve_pipe_spec(model)
        # row-sharded embedding engine (zoo.embed.sharded): resolved once
        # per loop too — it flips engaged layers' param spec to row
        # partitioning, which must happen before fit resolves shardings
        embed_hook = resolve_sharded_embeddings(model)

        def embed_scope():
            # intercept_layer_calls(None) would DISABLE outer scopes for
            # the duration — only open a scope when a hook resolved
            import contextlib

            from .engine import intercept_layer_calls
            if embed_hook is None:
                return contextlib.nullcontext()
            return intercept_layer_calls(embed_hook)
        spec = resolve_fused_loss(model, loss_fn)
        prev = TrainingLoop._last_fused_labels
        if spec is None:
            if prev is not None:
                # prev = the head=/vocab= dict of the LAST engaged
                # loop (zeroing the stale series); bounded by the
                # model architectures built in-process
                self._registry.gauge("zoo_train_fused_ce",  # zoolint: disable=ZL015 bounded label set
                                     self._FUSED_GAUGE_HELP,
                                     labels=prev).set(0)
                TrainingLoop._last_fused_labels = None

            def apply_loss(p, net_state, x, y, rng):
                with seq_attention_scope(seq_mode), \
                        pipe_intercept(pipe_spec, p, training=True), \
                        embed_scope():
                    yp, ns = model.apply(p, net_state, x, training=True,
                                         rng=rng)
                with jax.named_scope("zoo_loss"):
                    return loss_fn(y, yp), ns
            self._apply_loss = apply_loss
            return apply_loss
        log.info("fused LM-head cross-entropy engaged: head=%s vocab=%d%s "
                 "(zoo.train.fused_ce; the (N, V) logits tensor is never "
                 "materialized)", spec.head.name, spec.head.output_dim,
                 " VOCAB-SHARDED over the model axis" if spec.sharded
                 else " TIED to the token table" if spec.tied else "")
        labels = {"head": spec.head.name,
                  "vocab": str(spec.head.output_dim),
                  "sharded": "1" if spec.sharded else "0",
                  "tied": "1" if spec.tied else "0"}
        if prev is not None and prev != labels:
            # stale-series zeroing, same bounded head=/vocab= set
            self._registry.gauge("zoo_train_fused_ce",  # zoolint: disable=ZL015 bounded label set
                                 self._FUSED_GAUGE_HELP, labels=prev).set(0)
        # head/vocab identify the fused head (catalog row documents
        # the keys); bounded by the model architectures in-process
        self._registry.gauge("zoo_train_fused_ce", self._FUSED_GAUGE_HELP,  # zoolint: disable=ZL015 bounded label set
                             labels=labels).set(1)
        TrainingLoop._last_fused_labels = labels

        def apply_loss(p, net_state, x, y, rng):
            # scopes chain: the fused head's own intercept (opened inside
            # apply_and_loss) composes with the embedding hook
            with seq_attention_scope(seq_mode), \
                    pipe_intercept(pipe_spec, p, training=True), \
                    embed_scope():
                return spec.apply_and_loss(model, p, net_state, x, y,
                                           rng=rng)
        self._apply_loss = apply_loss
        return apply_loss

    def _remat_wrapper(self):
        """``zoo.train.remat`` (opt-in): wrap the per-step forward+loss in
        ``jax.checkpoint`` so the backward recomputes activations instead of
        saving them — 32k training can raise the batch instead of sitting
        at batch 1. ``true``/``dots`` keeps MXU outputs
        (``dots_with_no_batch_dims_saveable`` — recompute the cheap
        elementwise chains, keep the matmuls); ``full`` saves nothing
        (maximum memory relief, a full extra forward of recompute). See
        TRAINING.md "Long-context tuning" for the trade-off table."""
        from ....common.context import (FALSE_FLAG_SPELLINGS,
                                        TRUE_FLAG_SPELLINGS)
        mode = get_zoo_context().get("zoo.train.remat", False)
        if isinstance(mode, str):
            low = mode.strip().lower()
            if low in FALSE_FLAG_SPELLINGS or low == "none":
                return lambda f: f
            if low in TRUE_FLAG_SPELLINGS or low in (
                    "dots", "dots_with_no_batch_dims_saveable"):
                policy = jax.checkpoint_policies.\
                    dots_with_no_batch_dims_saveable
            elif low in ("full", "all", "nothing_saveable"):
                policy = jax.checkpoint_policies.nothing_saveable
            else:
                raise ValueError(f"zoo.train.remat must be "
                                 f"false|true|dots|full, got {mode!r}")
        elif mode:
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        else:
            return lambda f: f
        return lambda f: jax.checkpoint(f, policy=policy)

    def _sentinel_config(self) -> anomaly.SentinelConfig:
        """Resolve the anomaly-sentinel/grad-clip knobs ONCE per loop
        (like the fused-loss resolution): the step and the loop that
        dispatches it must agree on the step's signature, and with
        ``sentinel=off`` and no clipping the step is the historical one
        exactly — zero sentinel ops, bit-identical numerics."""
        if self._sentinel is None:
            self._sentinel = anomaly.resolve_config()
            cfg = self._sentinel
            if cfg.sentinel:
                log.info(
                    "anomaly sentinels armed (zoo.train.sentinel=%s): "
                    "nan-loss/nan-grad checks + grad-norm spike at %gx "
                    "EWMA%s%s", cfg.mode, cfg.spike_factor,
                    "; updates from anomalous steps are DISCARDED, "
                    "escalating to checkpoint rollback past "
                    f"{cfg.max_skips_per_epoch} skips/epoch"
                    if cfg.mode == "recover" else "",
                    "; train.grads fault injection compiled in"
                    if cfg.faults else "")
        return self._sentinel

    def _make_step_core(self):
        """The step's forward/backward/update. Returns ``(core_fn, cfg)``.

        With the sentinel layer inactive (``zoo.train.sentinel=off`` and
        no ``zoo.train.grad_clip``) the core is EXACTLY the historical
        step — no extra inputs, outputs, or ops, so the off mode
        preserves step numerics bit-for-bit. Active, the core grows a
        sentinel-state carry and a packed int32 flag output
        (``common/anomaly.py``): non-finite loss, non-finite/spiking
        global grad norm, clip engagement — computed on device inside
        the same fused program, no extra host sync. In ``recover`` mode
        an anomalous step's params/opt-state/net-state updates are
        discarded on device (the carry keeps the pre-step values); the
        host observes the flag later and handles budget escalation."""
        from .layers.self_attention import remat_saved_bytes
        opt = self.optimizer
        apply_loss = self._loss_application()
        remat = self._remat_wrapper()
        cfg = self._sentinel_config()

        def backward(params, net_state, x, y, rng):
            def lfn(p):
                l, ns = apply_loss(p, net_state, x, y, rng)
                with jax.named_scope("zoo_loss"):
                    aux = _aux_loss_sum(ns)
                    return (l if aux is None else l + aux), ns
            # trace time, once a compiled step: a rematerialised
            # DecoderStack counts what its checkpoints keep while the
            # gradient is traced; a model without one reads 0
            remat_saved_bytes({})
            out = jax.value_and_grad(remat(lfn), has_aux=True)(params)
            self._remat_saved = remat_saved_bytes()
            return out

        def update(params, opt_state, grads):
            with jax.named_scope("zoo_opt.update"):
                updates, opt_state = opt.update(grads, opt_state, params)
                opt_state = self._pin_opt_state(opt_state)
                return self._pin_params(
                    optax.apply_updates(params, updates)), opt_state

        if not cfg.active:
            def plain(params, opt_state, net_state, rng, x, y):
                (l, ns), grads = backward(params, net_state, x, y, rng)
                params, opt_state = update(params, opt_state, grads)
                return params, opt_state, ns, l
            return plain, cfg

        def guard(l, grads, sstate, fault):
            """The sentinels' checks and the clip (device scope
            ``zoo_opt.guard``): ``(loss, grads, sentinel state, flags)``."""
            if cfg.faults:
                # chaos only (zoo.faults.enabled at build time): apply
                # the host-scheduled train.grads poison code on device
                l, grads = anomaly.inject_grads(l, grads, fault[0],
                                                fault[1])
            gnorm = anomaly.global_norm(grads)
            if cfg.sentinel:
                flags, sstate = anomaly.check(l, gnorm, sstate,
                                              cfg.spike_factor)
            else:
                flags = jnp.zeros((), jnp.int32)
            if cfg.grad_clip > 0:
                grads, engaged = anomaly.clip_by_global_norm(
                    grads, gnorm, cfg.grad_clip)
                flags = flags | jnp.where(engaged, anomaly.GRAD_CLIPPED,
                                          0).astype(jnp.int32)
            return l, grads, sstate, flags

        def guarded(params, opt_state, net_state, sstate, rng, fault, x, y):
            (l, ns), grads = backward(params, net_state, x, y, rng)
            with jax.named_scope("zoo_opt.guard"):
                l, grads, sstate, flags = guard(l, grads, sstate, fault)
            if cfg.mode == "recover":
                # skip-batch: an anomalous step's update is not applied —
                # params/opt-state/net-state keep their pre-step values
                # (the optimizer count does not advance either, so the
                # surviving trajectory matches a run that never saw the
                # poison batch). lax.cond, not a where-select: the
                # healthy path must run EXACTLY the plain update — a
                # per-leaf select costs extra full passes over params +
                # moments every step (measured ~30% on the NCF bench
                # shape), while the untaken skip branch costs nothing
                with jax.named_scope("zoo_opt.guard"):
                    bad = (flags & anomaly.ANOMALY_MASK) > 0

                def _apply(operand):
                    p, o, g, new_ns = operand
                    return update(p, o, g) + (new_ns,)

                def _skip(operand):
                    p, o, _g, _new_ns = operand
                    return p, o, net_state

                params, opt_state, net_state = jax.lax.cond(
                    bad, _skip, _apply, (params, opt_state, grads, ns))
            else:
                params, opt_state = update(params, opt_state, grads)
                net_state = ns
            return params, opt_state, net_state, sstate, l, flags

        return guarded, cfg

    def build_train_step(self):
        core, cfg = self._make_step_core()
        # instrument_jit == jax.jit + compile accounting: every first
        # compile lands in zoo_jit_compile_*, every recompile under a new
        # batch shape emits a jit.retrace event naming the path
        self._train_step = instrument_jit(core, name="train.step",
                                          registry=self._registry,
                                          donate_argnums=(0, 1, 2))
        return self._train_step

    def _shard_opt_state(self, opt_state, psh, repl):
        """Committed placement for optimizer state: param-shaped leaves
        (adam moments) follow the param shardings, counters and the like
        replicate. Used for BOTH fresh and reused state so every fit call
        presents identical input shardings to the jitted step — otherwise
        the first call hands uncommitted counters while later calls hand
        committed ones, and each fit() misses the jit cache and recompiles
        the step (~20 s on a real chip).

        ``zoo.train.zero_sharding``: ZeRO-1 — moments additionally shard
        over the ``data`` axis (``mesh_lib.zero_sharding_for``); the jitted
        step re-pins the updated state each step so GSPMD keeps the
        reduce-scatter/all-gather form instead of drifting back to
        replicated."""
        zero = bool(get_zoo_context().get("zoo.train.zero_sharding", False))

        def moment_sharding(leaf, base):
            if not zero:
                return base
            return mesh_lib.zero_sharding_for(base, np.shape(leaf),
                                              self.mesh)

        try:
            shardings = optax.tree_map_params(
                self.optimizer, lambda s, sh: moment_sharding(s, sh),
                opt_state, psh,
                transform_non_params=lambda s: repl)
            # the sharding TREE (matching opt_state's structure) doubles as
            # the per-step constraint target under zero_sharding, and
            # wherever the params themselves are pinned (_pin_params)
            self._opt_state_shardings = (
                shardings if zero or self._param_shardings is not None
                else None)
            return jax.tree.map(lambda s, sh: jax.device_put(s, sh),
                                opt_state, shardings)
        except (ValueError, TypeError, AttributeError) as e:
            # structure quirks of custom/wrapped optimizers (e.g.
            # multi_transform label fns failing placeholder introspection):
            # replicated moments are correct — and identical under pure DP —
            # but under TP they reshard every step, so say so
            log.warning("could not apply param shardings to the optimizer "
                        "state (%s); moments stay replicated", e)
            self._opt_state_shardings = None
            return jax.device_put(opt_state, repl)

    def _pin_opt_state(self, opt_state):
        """In-step sharding constraint keeping ZeRO-sharded moments sharded
        from step to step, and moments on their params' shardings where
        those are pinned (no-op otherwise)."""
        sh = self._opt_state_shardings
        if sh is None:
            return opt_state
        return jax.tree.map(jax.lax.with_sharding_constraint, opt_state, sh)

    def _pin_params(self, params):
        """In-step sharding constraint keeping the updated params on their
        DECLARED shardings. Left free, GSPMD picks the step's output
        shardings itself: under tensor parallelism it model-shards leaves
        declared replicated (LayerNorm scales, biases), the next call
        then presents other input shardings than the first, and the whole
        step compiles a second time (seen on a four-chip v5e host, PR 21:
        two compilations of BERT-base in the first fit). No-op on meshes
        where every param replicates."""
        sh = self._param_shardings
        if sh is None:
            return params
        return jax.tree.map(jax.lax.with_sharding_constraint, params, sh)

    def build_eval_step(self):
        model, loss_fn, metrics = self.model, self.loss, self.metrics
        pe_loss = objectives.per_example_loss(loss_fn)

        def _update(m):
            """User Metric classes may predate the mask argument; detect the
            two-arg signature once (outside jit) and shim it."""
            try:
                import inspect
                n = len(inspect.signature(m.update).parameters)
            except (TypeError, ValueError):
                n = 3
            if n >= 3:
                return m.update
            return lambda y, yp, mask: m.update(y, yp)

        updates = [(m.name, _update(m)) for m in metrics]

        def step(params, net_state, x, y, mask):
            yp, _ = model.apply(params, net_state, x, training=False, rng=None)
            stats = {name: upd(y, yp, mask) for name, upd in updates}
            if pe_loss is not None:
                stats["loss"] = {"sum": jnp.sum(pe_loss(y, yp) * mask),
                                 "count": jnp.sum(mask)}
            else:
                # cross-batch losses (rank_hinge, custom callables) have no
                # per-example form; the whole-batch loss (which unavoidably
                # includes repeated-pad rows — for rank_hinge an odd real tail
                # also misaligns the assumed (pos, neg) pairing of pad rows)
                # is weighted by the real-row count so pads don't inflate it.
                stats["loss"] = {"sum": loss_fn(y, yp) * jnp.sum(mask),
                                 "count": jnp.sum(mask)}
            return stats

        self._eval_step = instrument_jit(step, name="train.eval_step",
                                         registry=self._registry)
        return self._eval_step

    def build_predict_step(self):
        model = self.model
        # multi-host: batch-sharded outputs span processes, which the host
        # cannot device_get; replicate them on-device (an all-gather over
        # ICI/DCN — the reference ships predictions back through Spark the
        # same way, Predictor.scala:136-208)
        gather = jax.process_count() > 1
        repl = (mesh_lib.replicated_sharding(self.mesh) if gather else None)

        def step(params, net_state, x):
            yp, _ = model.apply(params, net_state, x, training=False, rng=None)
            if gather:
                yp = jax.tree.map(
                    lambda a: jax.lax.with_sharding_constraint(a, repl), yp)
            return yp

        self._predict_step = instrument_jit(step, name="train.predict_step",
                                            registry=self._registry)
        return self._predict_step

    # -- observability ------------------------------------------------------
    def _maybe_compute_flops(self, args, batch_size: int) -> float:
        """One-shot XLA cost-analysis pass over the train step, caching
        FLOPs/example for the MFU gauge. Opt-in (``zoo.metrics.flops``): the
        extra ``lower().compile()`` costs a compile, wasted on backends with
        no known peak — and ``lower`` only reads avals/shardings, so calling
        it on buffers the subsequent dispatch donates is safe. Returns the
        seconds spent so the caller can exclude the compile from the
        epoch-timing window (the metrics this pass feeds must not be skewed
        by it). The same compiled object's text gives ``model.
        last_fit_report["step_census"]``: its instructions by device scope
        and pass (``observability/step_ledger.py``)."""
        if self._flops_per_example is not None:
            return 0.0
        if not get_zoo_context().get("zoo.metrics.flops", False):
            # do NOT latch the off state: the flag is re-read per dispatch
            # (one dict lookup) so enabling it before a later fit on the
            # same compiled model still produces an MFU reading
            return 0.0
        from ....utils import profiling
        self._gp_note("device_step")    # close the step interval first
        t = time.perf_counter()
        flops = None
        try:
            compiled = self._train_step.lower(*args).compile()
            flops = profiling.compiled_flops(compiled)
            self._step_census = step_ledger.census(compiled.as_text())
        except Exception:   # backend-dependent; never fail a fit for MFU
            pass
        # 0.0 latches "tried and unavailable" so the compile isn't retried
        self._flops_per_example = flops / batch_size if flops else 0.0
        self._gp_note("compile")
        return time.perf_counter() - t

    def _observe_fit_metrics(self, steps: int, dt: float,
                             n_examples: int) -> None:
        """Per-epoch registry update: weighted step-time histogram,
        records/sec gauge, cumulative counters, and — when FLOPs/example
        is known and the chip peak is published — achieved MFU via
        ``utils/profiling.py``."""
        if steps <= 0 or dt <= 0:
            return
        self._m_step_time.observe(dt / steps, n=steps)
        thr = n_examples / dt
        self._m_throughput.set(thr)
        self._m_steps.inc(steps)
        self._m_examples.inc(n_examples)
        if self._flops_per_example:
            from ....utils import profiling
            m = profiling.mfu(self._flops_per_example * thr)
            if m is not None:
                self._m_mfu.set(m)

    # -- checkpoint plumbing ------------------------------------------------
    def _ckpt_manager(self) -> Optional[CheckpointManager]:
        spec = getattr(self.model, "_checkpoint", None)
        if spec is None:
            return None
        ctx = get_zoo_context()
        keep = spec.get("keep")
        if keep is None:  # keep=0 means keep-all, so no falsy check
            keep = int(ctx.get("zoo.checkpoint.keep", 3))
        return CheckpointManager(spec["path"], keep=keep,
                                 registry=self._registry,
                                 ledger=self._goodput)

    def _ckpt_trigger(self) -> Trigger:
        spec = getattr(self.model, "_checkpoint", None) or {}
        return spec.get("trigger") or EveryEpoch()

    def _save_checkpoint(self, run: "_FitRun", sync: bool = False) -> None:
        """Cut a snapshot of the run's state where it stands. Async by
        default: the step path pays one host transfer and the
        serialization/commit rides the manager's writer thread;
        ``sync=True`` (the SIGTERM path) blocks until committed."""
        st = run.loop_state
        run.mgr.save(st.iteration,
                     {"params": run.params, "opt_state": run.opt_state,
                      "net_state": run.net_state},
                     meta={"epoch": st.epoch, "iteration": st.iteration,
                           "epoch_finished": st.epoch_finished},
                     sync=sync, mesh=mesh_lib.mesh_metadata(self.mesh))

    def _close_active_ckpt_mgr(self, surface: bool) -> None:
        """Join the active manager's in-flight save. ``surface=True``
        re-raises a background save failure (the end-of-fit surfacing
        point); ``surface=False`` is exception-path cleanup — the failure
        was already counted, and masking the in-flight exception with a
        second one would hide the real crash."""
        mgr, self._active_ckpt_mgr = self._active_ckpt_mgr, None
        if mgr is not None:
            mgr.close(raise_pending=surface)

    def _maybe_preempt(self, run: "_FitRun") -> None:
        """SIGTERM arrived (``zoo.checkpoint.on_sigterm``): cut one final
        SYNCHRONOUS checkpoint at this step boundary, publish in-memory
        state, and exit cleanly via :class:`TrainingPreempted`."""
        if run.mgr is None or not self._preempted.is_set():
            return
        iteration = run.loop_state.iteration
        log.warning("SIGTERM: cutting a final synchronous checkpoint at "
                    "iteration %d before exiting", iteration)
        try:
            self._save_checkpoint(run, sync=True)
        except Exception:
            # the process is going down either way; the newest previous
            # snapshot (already committed) remains the resume point
            log.exception("final preemption checkpoint failed")
        self._publish(run.params, run.net_state, run.opt_state)
        self.model.finished_iterations = iteration
        raise TrainingPreempted(
            f"training preempted by SIGTERM; final checkpoint cut at "
            f"iteration {iteration}")

    def _on_sigterm(self, signum, frame) -> None:
        grace = self._sigterm_grace
        if grace is not None:
            self._try_grace_cut(grace)      # raises when it cuts
        log.warning("SIGTERM received; requesting a final checkpoint at "
                    "the next step boundary")
        self._preempted.set()

    # -- SIGTERM grace budget (zoo.checkpoint.sigterm_grace_s) --------------
    def _segment_begin(self, run: "_FitRun") -> None:
        """A dispatch segment (one step) is about to enter the device.
        When the running duration estimate
        already exceeds the grace budget, clone the boundary state NOW —
        the dispatch donates these trees, so by the time the handler
        fires mid-segment the originals are deleted device buffers. A
        segment estimated to finish within the budget skips the clone
        (the handler just waits for the boundary), so the copy is only
        paid in the slow-segment regime it exists for."""
        if self._sigterm_grace is None or run.mgr is None:
            return
        est = self._segment_est
        if est is not None and est > self._sigterm_grace:
            st = run.loop_state
            self._boundary_ref = (
                run.mgr, st.iteration, st.epoch, st.epoch_finished,
                _clone_tree((run.params, run.opt_state, run.net_state)))
        else:
            self._boundary_ref = None
        self._segment_t0 = time.monotonic()

    def _segment_end(self) -> None:
        """Fold the completed segment's wall time into the EWMA estimate
        the handler projects the next boundary from. The loop's FIRST
        segment ever is discarded: it carries the one-time jit compile
        (tens of seconds), and folding it in would overestimate the next
        boundaries — paying boundary clones and cutting mid-epoch
        snapshots when the real boundary is milliseconds away (the
        training-side analogue of serving's ``_DOOMED_MIN_OBS``
        warm-up)."""
        if self._sigterm_grace is None:
            return
        t0 = self._segment_t0
        self._segment_t0 = None
        self._boundary_ref = None
        if t0 is None:
            return
        self._segment_count += 1
        if self._segment_count == 1:
            return                      # compile-contaminated sample
        dur = time.monotonic() - t0
        est = self._segment_est
        self._segment_est = dur if est is None else 0.5 * est + 0.5 * dur

    def _try_grace_cut(self, grace: float) -> None:
        """SIGTERM-handler path: when the estimated time to the next
        step boundary exceeds the grace budget, cut one synchronous
        snapshot of the LAST boundary's state immediately — mid-epoch —
        and exit via :class:`TrainingPreempted`, instead of gambling
        that the in-flight dispatch beats the preemption deadline. No
        estimate, no captured boundary, or a near boundary → return and
        let the normal next-boundary path run."""
        t0, est, ref = self._segment_t0, self._segment_est, \
            self._boundary_ref
        if t0 is None or est is None or ref is None:
            return
        eta = est - (time.monotonic() - t0)
        if eta <= grace:
            return
        # de-arm BEFORE the (multi-second) synchronous save: a supervisor
        # that re-sends SIGTERM while it runs re-enters this handler, and
        # a nested save of the same snapshot interleaved with the paused
        # outer one would corrupt exactly the checkpoint being cut — the
        # re-entrant call must fall through to the boundary-latch path
        self._boundary_ref = None
        self._segment_t0 = None
        mgr, iteration, epoch, epoch_finished, trees = ref
        params, opt_state, net_state = trees
        log.warning("SIGTERM: estimated %.2fs to the next step boundary "
                    "exceeds the %.2fs grace budget; cutting a mid-epoch "
                    "snapshot at iteration %d now", eta, grace, iteration)
        try:
            mgr.save(iteration,
                     {"params": params, "opt_state": opt_state,
                      "net_state": net_state},
                     meta={"epoch": epoch, "iteration": iteration,
                           "epoch_finished": epoch_finished},
                     sync=True, mesh=mesh_lib.mesh_metadata(self.mesh))
        except Exception:
            # going down either way; the newest committed snapshot
            # remains the resume point
            log.exception("grace-budget preemption checkpoint failed")
        # the boundary clone is the model's from here: the step in flight
        # has consumed the live trees
        self._publish(params, net_state, opt_state)
        self.model.finished_iterations = iteration
        raise TrainingPreempted(
            f"training preempted by SIGTERM; grace budget {grace:g}s is "
            f"shorter than the ~{eta:.2f}s to the next step boundary — "
            f"mid-epoch checkpoint cut at iteration {iteration}")

    def _try_resume(self, mgr: CheckpointManager, params, opt_state,
                    net_state, psh, repl, allow_regress: bool = False):
        """Restore the newest VALID snapshot (``Topology.scala:1220-1246``
        + manifest/checksum verification): a corrupt or uncommitted
        snapshot is quarantined and the restore falls back to the next
        one that verifies, so resume always lands on good weights.
        Returns (params, opt_state, net_state, meta) — inputs unchanged
        if there is nothing at or past the model's in-memory progress
        (never regress: a snapshot older than ``finished_iterations`` was
        cut mid-epoch before further completed epochs).

        **Elastic restore**: snapshot leaves are host-side and
        topology-free, so the restored trees are explicitly RE-PLACED
        under the CURRENT mesh — params under ``psh`` (computed by
        ``mesh_lib.param_shardings`` for this mesh, which re-validates
        divisibility with the coalesced replicated-fallback warning),
        net state replicated, optimizer state re-sharded through
        ``_shard_opt_state`` (ZeRO moments re-partition over the new
        ``data`` axis). A preempted ``{data:8}`` job therefore resumes
        on ``{data:4}`` or ``{data:1}`` with bit-identical host values;
        a mesh-metadata mismatch is REPORTED (log + ``ckpt.elastic_restore``
        event), never silently mis-sharded."""
        # allow_regress (the rollback path): going BACK past the model's
        # in-memory progress is the point — the in-memory state is the
        # diverging one being abandoned. The default keeps the
        # never-regress guard (a stale mid-epoch snapshot must not undo
        # later completed epochs on an ordinary resume/retry).
        out = mgr.restore_latest(
            {"params": params, "opt_state": opt_state,
             "net_state": net_state},
            min_step=None if allow_regress
            else self.model.finished_iterations)
        if out is None:
            return params, opt_state, net_state, None
        step, trees, meta = out
        saved_mesh = meta.get("mesh")
        cur_mesh = mesh_lib.mesh_metadata(self.mesh)
        if saved_mesh is not None and saved_mesh != cur_mesh:
            log.warning(
                "elastic restore: ckpt-%d was saved under mesh %s "
                "(%s device(s)) and is restoring under mesh %s "
                "(%d device(s)) — host leaves re-placed under the "
                "current shardings, optimizer state re-sharded",
                step, mesh_lib.format_mesh(saved_mesh),
                saved_mesh.get("devices", "?"),
                mesh_lib.format_mesh(cur_mesh), cur_mesh["devices"])
            self._registry.emit(
                "ckpt.elastic_restore", step=step,
                saved=mesh_lib.format_mesh(saved_mesh),
                restored=mesh_lib.format_mesh(cur_mesh))
        params = jax.device_put(trees["params"], psh)
        opt_state = self._shard_opt_state(trees["opt_state"], psh, repl)
        net_state = jax.device_put(trees["net_state"], repl)
        log.info("resumed from checkpoint ckpt-%d (epoch %s)", step,
                 meta.get("epoch"))
        return params, opt_state, net_state, meta

    # -- fit ---------------------------------------------------------------
    def fit(self, x, y, *, batch_size: int, nb_epoch: int,
            validation_data=None, rng=None,
            callbacks: Sequence[Callable[[Dict[str, Any]], None]] = (),
            shuffle: bool = True, end_trigger: Optional[Trigger] = None,
            ) -> Dict[str, List[float]]:
        ctx = get_zoo_context()
        fs = FeatureSet.array(x, y, shuffle=shuffle, seed=ctx.seed)
        return self.fit_feature_set(fs, batch_size=batch_size,
                                    nb_epoch=nb_epoch,
                                    validation_data=validation_data, rng=rng,
                                    callbacks=callbacks,
                                    end_trigger=end_trigger)

    def fit_feature_set(self, fs: FeatureSet, *, batch_size: int,
                        nb_epoch: int, validation_data=None, rng=None,
                        callbacks: Sequence[Callable] = (),
                        end_trigger: Optional[Trigger] = None,
                        ) -> Dict[str, List[float]]:
        """Train on a FeatureSet with retry-on-failure semantics
        (``Topology.scala:1171-1253``): any step failure reloads the latest
        checkpoint (when ``set_checkpoint`` is configured) and retries, at
        most ``zoo.failure.retry_times`` times per
        ``zoo.failure.retry_window_sec`` window."""
        ctx = get_zoo_context()
        retry_times = int(ctx.get("zoo.failure.retry_times", 5))
        window_sec = float(ctx.get("zoo.failure.retry_window_sec", 3600))
        attempts = 0
        window_start = time.time()
        # per-fit self-healing state (zoo.train.sentinel=recover): the
        # flagged-iteration set survives rollback attempts within this
        # fit (the replay must skip the offending window), and the
        # rollback RetryBudget bounds escalations so a persistent
        # divergence raises TrainingDiverged instead of looping forever
        sen = self._sentinel_config()
        self._anomalous_steps = set()
        self._rollback_pending = False
        self._gp_restarting = False
        self._rollback_budget = (
            RetryBudget(capacity=sen.max_rollbacks, deposit=0.0,
                        name="train.rollback", registry=self._registry)
            if sen.mode == "recover" else None)
        # the epoch target is fixed once, after any checkpoint resume inside
        # the first attempt — retries must not extend it
        target_holder: Dict[str, int] = {}
        # one-shot profiler capture (model.set_profile): trace this fit
        # call, retries included (profiling.trace no-ops on None)
        profile_dir = getattr(self.model, "_profile_dir", None)
        if profile_dir:
            self.model._profile_dir = None
        # preemption-safe shutdown (zoo.checkpoint.on_sigterm, opt-in):
        # SIGTERM during this fit requests one final synchronous snapshot
        # at the next step boundary, then exits via TrainingPreempted —
        # the TPU-preemption analogue of the reference's driver-failure
        # snapshot. Signal handlers only install on the main thread.
        self._preempted.clear()
        sig_installed = False
        prev_handler = None
        self._sigterm_grace = None
        self._segment_t0 = self._segment_est = None
        self._boundary_ref = None
        if (bool(ctx.get("zoo.checkpoint.on_sigterm", False))
                and getattr(self.model, "_checkpoint", None) is not None):
            if threading.current_thread() is threading.main_thread():
                prev_handler = signal.signal(signal.SIGTERM,
                                             self._on_sigterm)
                sig_installed = True
                # grace budget: with the estimated time-to-boundary
                # above this, the handler cuts a MID-EPOCH snapshot
                # immediately instead of waiting out a dispatch the
                # preemption deadline may not cover. Armed ONLY with the
                # handler installed — the segment tracking clones whole
                # param trees, a price with no payoff when no handler
                # can ever fire.
                grace = float(ctx.get("zoo.checkpoint.sigterm_grace_s", 0)
                              or 0)
                self._sigterm_grace = grace if grace > 0 else None
            else:
                log.warning("zoo.checkpoint.on_sigterm is set but fit() "
                            "is not on the main thread; SIGTERM "
                            "checkpointing disabled for this fit")
        from ....utils import profiling
        # goodput/badput ledger for this fit (zoo.goodput.enabled):
        # every wall-clock second between here and the finally below is
        # attributed to exactly one category
        t_open = time.perf_counter()
        self._probe = InflightProbe(self._registry)
        self._goodput = (GoodputLedger("train", registry=self._registry,
                                       device_busy=self._probe.busy)
                         if goodput_enabled() else None)
        if self._goodput is not None:
            self._goodput.open(t_open)
        host_before = self._phases.seconds()
        compile_before = xla_compile_totals()
        from .layers import moe
        # (a state that a failed step consumed has no counters to start
        # from: ``_open_fit`` restores it, or says that it cannot)
        moe_before = ({} if _any_deleted(self.model.net_state)
                      else moe.routed_layer_totals(self.model.net_state))
        try:
            with profiling.trace(profile_dir), span("train.fit",
                                                    registry=self._registry):
                return self._fit_with_retry(
                    fs, batch_size=batch_size, nb_epoch=nb_epoch,
                    target_holder=target_holder,
                    validation_data=validation_data, rng=rng,
                    callbacks=callbacks, end_trigger=end_trigger,
                    retry_times=retry_times, window_sec=window_sec,
                    attempts=attempts, window_start=window_start)
        finally:
            # close the ledger's last open interval — teardown is idle
            # (the epoch's tail drained the device: it holds no step)
            t_end = time.perf_counter()
            if self._goodput is not None:
                self._goodput.note("idle", t_end)
            self.model.last_fit_report = self._fit_report(
                t_open, t_end, host_before, compile_before)
            try:
                moe_report = moe.fit_report(moe_before, self.model.net_state,
                                            self._registry)
            except Exception:   # accounting must not mask the fit's own error
                log.exception("routed-layer counters could not be read")
                moe_report = None
            if moe_report is not None:
                self.model.last_fit_report["moe"] = moe_report
            self._probe.clear()
            # the boundary clone holds whole param trees — never past fit
            self._boundary_ref = None
            self._segment_t0 = None
            if sig_installed:
                # getsignal/signal return None for a handler not installed
                # from Python (an embedding runtime's C-level handler) —
                # None is not re-installable; SIG_DFL is the closest we
                # can restore without raising out of this finally
                signal.signal(signal.SIGTERM,
                              prev_handler if prev_handler is not None
                              else signal.SIG_DFL)

    def _fit_with_retry(self, fs, *, batch_size, nb_epoch, target_holder,
                        validation_data, rng, callbacks, end_trigger,
                        retry_times, window_sec, attempts, window_start):
        while True:
            try:
                with self._phases:
                    history = self._fit_impl(
                        fs, batch_size=batch_size, nb_epoch=nb_epoch,
                        target_holder=target_holder,
                        validation_data=validation_data, rng=rng,
                        callbacks=callbacks, end_trigger=end_trigger)
                # end-of-fit join of the async checkpoint writer: a
                # background save failure surfaces HERE (CheckpointSaveError
                # → the generic handler below, which re-cuts the lost
                # snapshot through the normal retry path)
                self._close_active_ckpt_mgr(surface=True)
                return history
            except KeyboardInterrupt:
                self._close_active_ckpt_mgr(surface=False)
                raise
            except _RollbackRequested as rb:
                # skip-budget escalation (zoo.train.sentinel=recover):
                # reload the last good snapshot and replay with the
                # flagged window skipped — bounded by the per-fit
                # rollback RetryBudget so a divergence the rollback
                # cannot outrun fails loudly instead of looping forever
                self._close_active_ckpt_mgr(surface=False)
                mgr = self._ckpt_manager()
                if mgr is None or mgr.latest() is None:
                    raise TrainingDiverged(
                        f"{rb} — and no checkpoint is configured/"
                        f"committed to roll back to "
                        f"(model.set_checkpoint enables recovery)") from rb
                budget = self._rollback_budget
                if budget is None or not budget.withdraw():
                    raise TrainingDiverged(
                        f"{rb} — rollback budget exhausted "
                        f"(zoo.train.max_rollbacks); the model holds the "
                        f"last known-good state") from rb
                # unwind cost up to here is replay overhead on the ledger
                self._gp_note("rollback_replay")
                self._m_rollback.inc()
                self._registry.emit("train.rollback", epoch=rb.epoch,
                                    skips=rb.skips,
                                    restore_step=mgr.latest(),
                                    skipped_iters=len(self._anomalous_steps))
                log.warning(
                    "training diverging (%s); rolling back to ckpt-%s and "
                    "replaying with %d flagged step(s) skipped", rb,
                    mgr.latest(), len(self._anomalous_steps))
                # the next _fit_impl attempt restores via _try_resume —
                # with regression past the in-memory progress allowed
                # (rolling BACK is the point) — and skips
                # self._anomalous_steps on replay
                self._rollback_pending = True
            except (ValueError, TypeError):
                # user/config errors are not transient — the reference likewise
                # excludes IllegalArgumentException from its retry loop
                # (Topology.scala:1171-1253)
                self._close_active_ckpt_mgr(surface=False)
                raise
            except Exception:
                self._close_active_ckpt_mgr(surface=False)
                mgr = self._ckpt_manager()
                if mgr is None or mgr.latest() is None:
                    raise  # nothing to recover from
                if time.time() - window_start > window_sec:
                    attempts = 0
                    window_start = time.time()
                attempts += 1
                if attempts > retry_times:
                    log.exception("giving up after %d failed attempts", attempts)
                    raise
                log.warning("training step failed (attempt %d/%d); reloading "
                            "latest checkpoint and retrying", attempts,
                            retry_times, exc_info=True)
                # failed-attempt unwind + upcoming reload is restart cost
                self._gp_note("restart")
                self._gp_restarting = True
                # the next _fit_impl attempt restores params/opt_state from
                # the latest snapshot via _try_resume
            except BaseException:
                # TrainingPreempted (SystemExit): the final sync snapshot is
                # already committed — just release the writer and exit
                self._close_active_ckpt_mgr(surface=False)
                raise

    def _fit_impl(self, fs: FeatureSet, *, batch_size: int, nb_epoch: int,
                  target_holder: Dict[str, int], validation_data=None,
                  rng=None, callbacks: Sequence[Callable] = (),
                  end_trigger: Optional[Trigger] = None,
                  ) -> Dict[str, List[float]]:
        """One attempt of a fit: open it, then run and close one epoch
        after another until the epoch target or the end trigger."""
        run = self._open_fit(fs, batch_size=batch_size, nb_epoch=nb_epoch,
                             target_holder=target_holder,
                             validation_data=validation_data, rng=rng,
                             end_trigger=end_trigger)
        try:
            # an empty range (nb_epoch=0) is a clean no-op
            for epoch in range(self.model.finished_epochs + 1,
                               run.target_epoch + 1):
                losses = self._run_epoch(run, epoch)
                if not self._close_epoch(run, epoch, losses,
                                         validation_data, callbacks):
                    break
        except BaseException:
            # the attempt ends mid-epoch (a failed step, an exception in
            # the stream, a save or a callback). Trees of the model's that
            # no step has taken stay: the last boundary's copy (what a
            # retry goes on from), what a preemption published, or the
            # loop's own before a step ran. Where a step took them the
            # model gets the live trees, its progress counted as at the
            # last boundary; unless those are gone too (the failed step
            # had consumed them, or it failed on the device and they are
            # its outputs): the model then holds deleted arrays, and the
            # next attempt restores a checkpoint or says that none is
            # configured (``_open_fit``)
            model, live = self.model, (run.params, run.net_state,
                                       run.opt_state)
            if _any_deleted((model.params, model.net_state,
                             model.opt_state)) and _usable(live):
                self._publish(*live)
            raise
        return run.history

    def _publish(self, params, net_state, opt_state,
                 copy: bool = False) -> None:
        """Hand the loop's state to the model, by reference: no copy. The
        arrays are the ones the next step of this fit, or the next fit,
        consumes (``jax.device_get(model.params)``, or a ``tfpark``
        model's ``get_weights()``, gives a copy that lasts). ``copy``
        says that the trees are a copy no step will take: the model's to
        keep until the next publish."""
        model = self.model
        model.params, model.net_state, model.opt_state = (
            params, net_state, opt_state)
        self._state_report["published"] = ("cloned" if copy
                                           else "handed_back")

    # -- fit, part 1: before the first epoch -------------------------------
    def _open_fit(self, fs: FeatureSet, *, batch_size: int, nb_epoch: int,
                  target_holder: Dict[str, int], validation_data, rng,
                  end_trigger: Optional[Trigger]) -> _FitRun:
        """What a fit attempt does before its first epoch: round the batch
        size to the mesh, initialise weights and state, build the step,
        take the model's trees over (no clone: from here to the end of the
        fit the loop holds the one copy, and arrays read from the model
        before are consumed by the first step), reuse or reset the
        optimizer state, resume from a checkpoint, and fix the epoch
        target."""
        ctx = get_zoo_context()
        model = self.model
        self._phases.switch("fit.enter")
        # fail NOW, not after an epoch of compute: validation/evaluate
        # need one dense array
        if getattr(validation_data, "ragged", False):
            raise ValueError(
                "bucketed validation_data is not supported — evaluate per "
                "bucket (validation_data.buckets) instead")
        if (getattr(self.loss, "__name__", "") == "rank_hinge"
                and getattr(fs, "shuffle", False)):
            log.warning(
                "rank_hinge consumes consecutive (positive, negative) rows, "
                "but this FeatureSet shuffles — the pairing is scrambled and "
                "the loss is meaningless; train with "
                "FeatureSet.array(..., shuffle=False)")
        dp = mesh_lib.data_parallel_size(self.mesh)
        if batch_size % dp != 0:
            rounded = _round_up(batch_size, dp)
            log.warning("batch_size %d not divisible by data-parallel size %d; "
                        "rounding up to %d", batch_size, dp, rounded)
            batch_size = rounded

        if model.params is None:
            model.init_weights(rng=rng, sample_input=fs.sample(1))
        if not model.net_state:
            # weights installed by hand and the state reset: the layers
            # that keep state start from their own (none: {} again), so
            # that the step sees one state structure from its first call
            model.net_state = model.initial_state()
        if self._train_step is None:
            self.build_train_step()

        repl = mesh_lib.replicated_sharding(self.mesh)
        # params: replicated under pure DP; sharded over the model axis when
        # the mesh has one (layers declare the specs — SURVEY §2.4 TP)
        psh = mesh_lib.param_shardings(model, model.params, self.mesh)
        self._param_shardings = psh if any(
            not s.is_fully_replicated for s in jax.tree.leaves(psh)) else None
        mgr = self._ckpt_manager()
        if _any_deleted((model.params, model.net_state)):
            # a step of an earlier attempt took the state and failed: only
            # a snapshot can bring it back (shapes are all the restore
            # needs of its templates)
            if mgr is None or mgr.latest() is None:
                raise RuntimeError(
                    "the model's weights were consumed by a training step "
                    "that failed, and no checkpoint is configured to "
                    "restore them from: initialise or load weights again")
            self._rollback_pending = True       # any snapshot will do
            model.params, model.net_state = jax.tree.map(
                lambda a: np.zeros(a.shape, a.dtype),
                (model.params, model.net_state))
            model.opt_state = None
        # no clone: device_put of trees already placed so is an alias, and
        # the donating step then consumes the model's own buffers. The
        # model is pointed at the loop's trees at once, so that nothing
        # keeps a second set alive; _publish hands back what they become
        handed = jax.tree_util.tree_leaves((model.params, model.net_state))
        params, net_state = _unshared(
            (jax.device_put(model.params, psh),
             jax.device_put(model.net_state, repl)))
        opt_state = self._initial_opt_state(params, psh, repl)
        self._state_report = {
            "source": ("handed_over" if all(
                a is b for a, b in zip(handed, jax.tree_util.tree_leaves(
                    (params, net_state)))) else "placed")}
        model.params, model.net_state, model.opt_state = (
            params, net_state, opt_state)
        del handed

        # resume: if a checkpoint directory is configured and holds a snapshot
        # newer than this model's progress, restore it (process-death resume)
        # registered so _fit_with_retry can join/close the async writer on
        # every exit path (including exceptions and preemption)
        self._active_ckpt_mgr = mgr
        if mgr is not None:
            was = params
            params, opt_state, net_state = self._resume_progress(
                mgr, params, opt_state, net_state, psh, repl)
            if params is not was:
                self._state_report["source"] = "restored"
                model.params, model.net_state, model.opt_state = (
                    params, net_state, opt_state)
            del was
        self._state_report["bytes"] = _tree_bytes(
            (params, net_state, opt_state))

        # sliced disk tier: one loop "epoch" is ONE slice pass; nb_epoch and
        # EveryEpoch-style triggers count FULL passes of num_of_slice slices
        # (DiskFeatureSet + ZooTrigger.scala:44-66 slice awareness)
        n_slices = int(getattr(fs, "num_of_slice", 1) or 1)
        ckpt_trigger = self._ckpt_trigger()
        if n_slices > 1:
            ckpt_trigger = _slice_aware(ckpt_trigger, n_slices)
            end_trigger = _slice_aware(end_trigger, n_slices)
        if "target" not in target_holder:
            # "train nb_epoch more" counts from post-resume progress, matching
            # the reference's getFinishedEpoch continuation (Topology.scala:373-386)
            target_holder["target"] = (model.finished_epochs
                                       + nb_epoch * n_slices)

        # anomaly sentinels (docs/guides/TRAINING.md): active ⇒ the step
        # carries EWMA state + packed flags, which the monitor holds
        sen = self._sentinel_config()
        return _FitRun(
            fs=fs, batch_size=batch_size, params=params,
            opt_state=opt_state, net_state=net_state,
            base_rng=rng if rng is not None else ctx.rng(), mgr=mgr,
            ckpt_trigger=ckpt_trigger, end_trigger=end_trigger,
            target_epoch=target_holder["target"],
            loop_state=TrainLoopState(iteration=model.finished_iterations,
                                      epoch=model.finished_epochs + 1),
            monitor=_SentinelMonitor(self, sen) if sen.active else None)

    def _initial_opt_state(self, params, psh, repl):
        """The optimizer state a fit starts from: the model's stored state
        only when it structurally matches the CURRENT optimizer — a
        clipping/optimizer change between train calls
        (Estimator.scala:75-100) alters the optax state tree, and feeding
        the old one would corrupt the update — else a fresh one."""
        stored = self.model.opt_state
        if stored is not None:
            # eval_shape: the CURRENT optimizer's state structure, zero
            # allocation
            fresh_struct = jax.tree_util.tree_structure(
                jax.eval_shape(self.optimizer.init, params))
            if jax.tree_util.tree_structure(stored) == fresh_struct:
                # handed over like the weights: no clone
                return self._shard_opt_state(_unshared(stored), psh, repl)
            log.warning("optimizer structure changed since the last fit; "
                        "resetting optimizer state")
        return self._shard_opt_state(self.optimizer.init(params), psh, repl)

    def _resume_progress(self, mgr: CheckpointManager, params, opt_state,
                         net_state, psh, repl):
        """Restore the newest snapshot at or past the model's progress (any
        snapshot, on a rollback) and set the model's epoch and iteration
        counts from it. Returns the three trees, restored or as given."""
        model = self.model
        rollback = self._rollback_pending
        self._rollback_pending = False
        params, opt_state, net_state, meta = self._try_resume(
            mgr, params, opt_state, net_state, psh, repl,
            allow_regress=rollback)
        # restore work belongs to the recovery path that demanded
        # it; a clean first attempt's resume probe is just spin-up
        self._gp_note("rollback_replay" if rollback
                      else "restart" if self._gp_restarting
                      else "idle")
        self._gp_restarting = False
        if meta is not None and meta.get("epoch") is not None:
            resumed_epoch = int(meta["epoch"]) - (
                0 if meta.get("epoch_finished") else 1)
            # a rollback REGRESSES the in-memory progress to the
            # restored snapshot — the abandoned later epochs retrain
            # (with the flagged windows skipped)
            if rollback or resumed_epoch > model.finished_epochs:
                model.finished_epochs = resumed_epoch
            model.finished_iterations = int(meta.get(
                "iteration", model.finished_iterations))
        elif rollback:
            log.warning("rollback requested but no snapshot could be "
                        "restored; continuing from the in-memory "
                        "state (further anomalies will re-escalate "
                        "within the rollback budget)")
        return params, opt_state, net_state

    # -- fit, part 2: one epoch's steps -------------------------------------
    def _run_epoch(self, run: _FitRun, epoch: int) -> List[Any]:
        """Stream one epoch's batches through the step. Returns the losses
        of the steps dispatched, still on the device; ``run.stop`` says
        whether the end trigger cut the epoch short."""
        # epoch-boundary overhead (metrics, callbacks, validation of
        # the previous epoch) since the last step lands on idle: the
        # previous epoch's tail drained the device
        self._gp_note("idle")
        self._phases.switch()       # fit.enter or epoch.publish ends
        run.epoch_t0 = time.time()
        st, mon = run.loop_state, run.monitor
        st.epoch = epoch
        # clear the boundary flag: mid-epoch trigger checks must not see
        # the previous epoch's True (stale EveryEpoch/MaxEpoch fires)
        st.epoch_finished = False
        if mon is not None:
            mon.begin_epoch(epoch, st.iteration)
        stream = prefetch_to_device(
            run.fs.iter_batches(run.batch_size,
                                epoch=get_zoo_context().seed + epoch,
                                drop_last=True),
            self.mesh, ledger=self._goodput, phases=self._phases)
        throttle_cpu = jax.default_backend() == "cpu"
        losses: List[Any] = []
        for bx, by in stream:
            if mon is not None and mon.flagged(st.iteration):
                # rollback replay: the offending data window is NOT
                # re-dispatched (its step was flagged before the
                # rollback); iteration still advances so the rng
                # schedule and the triggers stay aligned with the
                # original attempt
                st.iteration += 1
                mon.note_replay_skip()
                self._gp_note("anomaly_skip")
            else:
                losses.append(self._run_step(run, bx, by))
                # XLA:CPU only — bound host run-ahead. Its in-process
                # collective rendezvous aborts (40 s timeout) when dozens
                # of slow queued programs starve some device threads;
                # blocking every few dispatches caps the queue. Real TPU
                # runtimes pipeline deeply and stay unthrottled.
                if throttle_cpu and len(losses) % 4 == 0:
                    jax.block_until_ready(losses[-1])
            if self._step_boundary(run):
                run.stop = True
                # a mid-epoch stop leaves the pipeline suspended at its
                # yield: end it here, and with it its last ledger interval
                # (left to the collector, it would close after the tail)
                stream.close()
                break
        return losses

    def _run_step(self, run: _FitRun, bx, by):
        """Dispatch one optimizer step: THE place the loop calls the
        compiled program. Returns the step's loss (a device scalar)."""
        st, mon = run.loop_state, run.monitor
        it = st.iteration
        rng = jax.random.fold_in(run.base_rng, it)
        args = (run.params, run.opt_state, run.net_state) + (
            (rng, bx, by) if mon is None else mon.step_args(rng, bx, by))
        run.epoch_t0 += self._maybe_compute_flops(args, run.batch_size)
        self._segment_begin(run)
        out = self._dispatch(self._train_step, it, *args)
        self._segment_end()
        if mon is not None:
            out = mon.took(it, out)
        run.params, run.opt_state, run.net_state, loss = out
        st.iteration = it + 1
        return loss

    def _step_boundary(self, run: _FitRun) -> bool:
        """After every step of the stream, dispatched or skipped on a
        replay: checkpoint if its trigger fired, exit if SIGTERM asked
        for it, and say whether the end trigger fired."""
        st = run.loop_state
        if run.mgr is not None and run.ckpt_trigger(st):
            self._save_checkpoint(run)
        self._maybe_preempt(run)
        return run.end_trigger is not None and run.end_trigger(st)

    # -- fit, part 3: the epoch's tail --------------------------------------
    def _close_epoch(self, run: _FitRun, epoch: int, losses: List[Any],
                     validation_data, callbacks: Sequence[Callable]) -> bool:
        """Wait for the epoch's steps, then everything that happens once
        an epoch: its loss, the boundary checkpoint, the state handed to
        the model (by reference: validation and the callbacks read the
        loop's own trees, which the next epoch's first step consumes; a
        copy only for the retry loop, see below), validation, summaries,
        the record and the callbacks. Returns whether the fit goes on."""
        model, st, mon = self.model, run.loop_state, run.monitor
        completed = not run.stop    # a stop means the epoch was cut short
        mean_loss = self._drain(losses, reduce=mon is None)
        self._phases.switch("epoch.tail")
        loss_vec = None
        if mon is not None:
            # drain every pending flag first (escalation may raise
            # here, BEFORE the boundary checkpoint below); in recover
            # mode skipped steps' losses were never applied and are
            # excluded from the epoch mean
            loss_vec = _host_losses(losses)
            lmask = mon.loss_mask(len(loss_vec))
            epoch_loss = (float(loss_vec[lmask].mean()) if lmask.any()
                          else float("nan"))
        else:
            epoch_loss = float(mean_loss) if losses else float("nan")
        dt = time.time() - run.epoch_t0
        self._phases.switch("epoch.publish")
        n_seen = len(losses) * run.batch_size
        self._observe_fit_metrics(len(losses), dt, n_seen)
        run.history["loss"].append(epoch_loss)
        st.epoch_finished = completed
        if hasattr(run.end_trigger, "record"):
            run.end_trigger.record(epoch_loss)
        # cut a snapshot at the trigger, or unconditionally on a mid-epoch
        # stop so the truncated epoch's progress survives (its meta says
        # epoch_finished=False, so a resume retrains that epoch)
        if run.mgr is not None and (run.stop or run.ckpt_trigger(st)):
            self._save_checkpoint(run)

        # publish progress every epoch: no step is in flight, and the
        # model's trees are the loop's until the next epoch's first step
        # takes them (a callback that keeps arrays copies them). With a
        # checkpoint directory and another epoch to come the model gets a
        # copy instead: what a retry attempt falls back to when the
        # newest snapshot is older or torn. The copy of the boundary
        # before goes first, so that there are never three sets
        live = (run.params, run.net_state, run.opt_state)
        if run.mgr is not None and completed and epoch < run.target_epoch:
            model.params = model.net_state = model.opt_state = None
            try:
                self._publish(*_clone_tree(live), copy=True)
            except BaseException:
                self._publish(*live)
                raise
        else:
            self._publish(*live)
        if completed:
            model.finished_epochs = epoch
        model.finished_iterations = st.iteration

        record = {"epoch": epoch, "loss": epoch_loss,
                  "iteration": st.iteration,
                  "throughput": n_seen / dt if dt > 0 else 0.0,
                  "params": model.params, "opt_state": model.opt_state,
                  "net_state": model.net_state, "loop_state": st}
        val = None
        if validation_data is not None:
            if isinstance(validation_data, FeatureSet):
                vx, vy = validation_data.x, validation_data.y
            else:
                vx, vy = validation_data
            val = self.evaluate(vx, vy, batch_size=run.batch_size)
            for k, v in val.items():
                run.history.setdefault("val_" + k, []).append(v)
            record.update({"val_" + k: v for k, v in val.items()})
        tb = getattr(model, "_train_summary", None)
        if tb is not None:
            self._write_train_summary(
                tb, run, epoch, completed, record["throughput"],
                loss_vec if loss_vec is not None else _host_losses(losses))
        vtb = getattr(model, "_val_summary", None)
        if vtb is not None and val is not None:
            for k, v in val.items():
                vtb.add_scalar(k, float(v), st.iteration)
            vtb.writer.flush()
        log.info("Epoch %d%s: loss=%.6f (%.1f ex/s)%s", epoch,
                 "" if completed else " (stopped mid-epoch)", epoch_loss,
                 record["throughput"],
                 "".join(f" val_{k}={v:.4f}" for k, v in
                         (val.items() if val is not None else ())))
        for cb in callbacks:
            cb(record)
        # epoch_finished stays True through this boundary check (it is
        # cleared at the next epoch's start): MaxEpoch must see the
        # finished count, else a satisfied end trigger runs one extra
        # partial epoch
        return not (run.stop or (run.end_trigger is not None
                                 and run.end_trigger(st)))

    def _write_train_summary(self, tb, run: _FitRun, epoch: int,
                             completed: bool, throughput: float,
                             loss_vec: np.ndarray) -> None:
        """The epoch's TensorBoard scalars: one Loss point per optimizer
        step (the reference's per-iteration granularity), written at epoch
        end so no device sync lands inside the dispatch pipeline."""
        iteration, mon = run.loop_state.iteration, run.monitor
        if mon is not None and len(mon.epoch_step_iters) == len(loss_vec):
            # replay-skipped windows advance the iteration counter
            # without recording losses — the monitor's per-step
            # iteration log keeps each point on its real x position
            loss_its = [i + 1 for i in mon.epoch_step_iters]
        else:
            start_it = iteration - len(loss_vec)
            loss_its = [start_it + j + 1 for j in range(len(loss_vec))]
        for j, lv in enumerate(loss_vec):
            tb.add_scalar("Loss", float(lv), loss_its[j])
        tb.add_scalar("Throughput", throughput, iteration)
        lr = getattr(self.model, "_lr", None)
        if callable(lr):
            tb.add_scalar("LearningRate", float(lr(iteration)), iteration)
        elif isinstance(lr, (int, float)):
            tb.add_scalar("LearningRate", float(lr), iteration)
        if completed:
            # a mid-epoch end_trigger stop retrains this epoch on
            # the next fit(); logging its partial params here
            # would put two histograms under one epoch number
            _write_param_histograms(tb, self.model.params, epoch,
                                    iteration, n_steps=len(loss_vec))
        tb.writer.flush()

    # -- evaluate / predict -------------------------------------------------
    def _padded_batches(self, x, y, eff_bs: int, dp: int, *, with_mask: bool):
        """Padded fixed-size batches (+ per-row validity mask) for eval and
        predict — the host-side generator behind the prefetch pipeline."""
        for bx, by in iter_batches(x, y, eff_bs, shuffle=False, seed=0,
                                   drop_last=False):
            n = _num_examples(bx)
            padded = _round_up(n, dp)
            if n != padded:
                bx = _pad_to(bx, padded)
                by = None if by is None else _pad_to(by, padded)
            if with_mask:
                # padded tail rows are masked out of every statistic
                mask = np.concatenate([np.ones(n, np.float32),
                                       np.zeros(padded - n, np.float32)])
                yield bx, by, mask
            else:
                yield bx

    def evaluate(self, x, y=None, *, batch_size: int = 32) -> Dict[str, float]:
        if isinstance(x, FeatureSet):
            x, y = x.x, x.y
        model = self.model
        if self._eval_step is None:
            self.build_eval_step()
        totals = None
        dp = mesh_lib.data_parallel_size(self.mesh)
        eff_bs = _round_up(max(batch_size, dp), dp)
        # stream through the same prefetch pipeline as training; keep the
        # running totals on device so no step blocks on a host sync
        steps = 0
        with span("train.evaluate", registry=self._registry):
            t0 = time.perf_counter()
            stream = prefetch_to_device(
                self._padded_batches(x, y, eff_bs, dp, with_mask=True),
                self.mesh)
            for bx_d, by_d, mask_d in stream:
                stats = self._eval_step(model.params, model.net_state, bx_d,
                                        by_d, mask_d)
                totals = stats if totals is None else jax.tree.map(
                    lambda a, b: a + b, totals, stats)
                steps += 1
            out = {}
            if totals is None:
                return out
            totals = jax.device_get(totals)
            # registry update (the eval twin of _observe_fit_metrics): one
            # weighted observation per streamed step, record count from the
            # mask sum so pad rows never inflate it
            dt = time.perf_counter() - t0
            if steps and dt > 0:
                self._m_eval_step_time.observe(dt / steps, n=steps)
            self._m_eval_records.inc(int(totals["loss"]["count"]))
        for m in self.metrics:
            out[m.name] = float(m.finalize(totals[m.name]))
        out["loss"] = float(totals["loss"]["sum"] / max(totals["loss"]["count"], 1.0))
        return out

    def predict(self, x, *, batch_size: int = 32):
        if isinstance(x, FeatureSet):
            x = x.x
        model = self.model
        if self._predict_step is None:
            self.build_predict_step()
        dp = mesh_lib.data_parallel_size(self.mesh)
        eff_bs = _round_up(max(batch_size, dp), dp)
        n_total = _num_examples(x)
        sizes = [min(eff_bs, n_total - i) for i in range(0, n_total, eff_bs)]
        # keep a small window of batches in flight: dispatch stays ahead of
        # the host transfer (no per-batch sync) while device memory stays
        # bounded at `window` batches instead of O(dataset)
        window = 4
        pending: collections.deque = collections.deque()
        outs = []

        def drain_one():
            yp, n = pending.popleft()
            outs.append(jax.tree.map(lambda a: a[:n], jax.device_get(yp)))

        with span("train.predict", registry=self._registry):
            t0 = time.perf_counter()
            stream = prefetch_to_device(
                self._padded_batches(x, None, eff_bs, dp, with_mask=False),
                self.mesh)
            for i, bx_d in enumerate(stream):
                pending.append((self._predict_step(
                    model.params, model.net_state, bx_d), sizes[i]))
                if len(pending) > window:
                    drain_one()
            while pending:
                drain_one()
            # registry update mirrors evaluate's: weighted per-batch step
            # time + the REAL example count (pads excluded by `sizes`)
            dt = time.perf_counter() - t0
            if sizes and dt > 0:
                self._m_predict_step_time.observe(dt / len(sizes),
                                                  n=len(sizes))
            self._m_predict_records.inc(n_total)
        if not outs:
            return None
        return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)


def _first_dim(x):
    if isinstance(x, (list, tuple)):
        return x[0].shape[0]
    return x.shape[0]


# ---------------------------------------------------------------------------
# KerasNet facade: compile / fit / evaluate / predict
# (attached here so engine.py stays free of optimizer machinery)
# ---------------------------------------------------------------------------

def _compile(self: KerasNet, optimizer="adam", loss="mse", metrics=None,
             clip_norm: Optional[float] = None,
             clip_value: Optional[float] = None, **opt_kwargs):
    """``KerasNet.compile`` (``Topology.scala:135``)."""
    opt = optim_lib.get_optimizer(optimizer, **opt_kwargs)
    opt = optim_lib.with_clipping(opt, clip_norm=clip_norm, clip_value=clip_value)
    loss_fn = objectives.get_loss(loss)
    ms = [metrics_lib.get_metric(m) for m in (metrics or [])]
    self._compiled = CompiledSpec(opt, loss_fn, ms)
    self._loop = TrainingLoop(self, opt, loss_fn, ms)
    # effective lr (constant or schedule) for the LearningRate summary
    self._lr = optim_lib.resolve_lr(optimizer, **opt_kwargs)
    return self


def _init_weights(self: KerasNet, rng=None, input_shape=None, sample_input=None):
    """Materialize params/state. Shape comes from (in order) explicit
    ``input_shape``, a ``sample_input`` batch, or the declared layer shapes."""
    ctx = get_zoo_context()
    rng = rng if rng is not None else ctx.rng()
    shape = input_shape
    if shape is None and sample_input is not None:
        xs = sample_input if isinstance(sample_input, (list, tuple)) else [sample_input]
        shapes = [(None,) + tuple(np.asarray(a).shape[1:]) for a in xs]
        shape = shapes if len(shapes) > 1 else shapes[0]
    if shape is None:
        shape = self.input_shape
    params = self.build(rng, shape)
    state = self.initial_state(shape)
    self.params = params
    self.net_state = state
    return self


def _set_checkpoint(self: KerasNet, path: str, trigger: Optional[Trigger] = None,
                    keep: Optional[int] = None):
    """``KerasNet.setCheckpoint`` (``Topology.scala:245-255``): snapshot
    params + optimizer state + net state into ``path`` whenever ``trigger``
    fires (default: every epoch, ``Topology.scala:1161-1168``)."""
    self._checkpoint = {"path": path, "trigger": trigger, "keep": keep}
    return self


def _set_tensorboard(self: KerasNet, log_dir: str, app_name: str,
                     parameters_every_epochs: Optional[int] = None):
    """``setTensorBoard(logDir, appName)`` (``Topology.scala:204-216``):
    write train scalars (Loss per iteration, Throughput, LearningRate) to
    ``<log_dir>/<app_name>/train`` and validation metrics to
    ``.../validation`` as TensorBoard event files.

    ``parameters_every_epochs=N`` additionally writes per-layer weight
    HISTOGRAMS every N epochs (the reference's
    ``TrainSummary.setSummaryTrigger("Parameters", ...)`` +
    ``Summary.scala`` histogram path)."""
    from ....utils.tensorboard import TrainSummary, ValidationSummary
    for attr in ("_train_summary", "_val_summary"):
        old = getattr(self, attr, None)
        if old is not None:  # redirecting: release the previous file handle
            old.close()
    self._train_summary = TrainSummary(log_dir, app_name)
    if parameters_every_epochs is not None:
        self._train_summary.set_summary_trigger("Parameters",
                                                parameters_every_epochs)
    self._val_summary = ValidationSummary(log_dir, app_name)
    return self


def _set_profile(self: KerasNet, log_dir: str):
    """Capture a ``jax.profiler`` trace of the NEXT ``fit`` call into
    ``log_dir`` (one-shot) — view with TensorBoard's profile plugin/xprof.
    The sampling-profiler capability the reference never had (SURVEY §5:
    "no sampling profiler, no trace files")."""
    self._profile_dir = log_dir
    return self


def _get_train_summary(self: KerasNet, tag: str = "Loss") -> np.ndarray:
    """``getTrainSummary(tag)`` (``Topology.scala:222-229``): (n, 3) rows of
    ``[iteration, value, wall_time]``."""
    if getattr(self, "_train_summary", None) is None:
        raise RuntimeError("call set_tensorboard() before reading summaries")
    return self._train_summary.read_scalar(tag)


def _get_validation_summary(self: KerasNet, tag: str) -> np.ndarray:
    """``getValidationSummary(tag)`` (``Topology.scala:231-236``)."""
    if getattr(self, "_val_summary", None) is None:
        raise RuntimeError("call set_tensorboard() before reading summaries")
    return self._val_summary.read_scalar(tag)


def _fit(self: KerasNet, x, y=None, batch_size: int = 32, nb_epoch: int = 10,
         validation_data=None, shuffle: bool = True, rng=None, callbacks=(),
         end_trigger: Optional[Trigger] = None):
    """``KerasNet.fit`` (``Topology.scala:418``). ``x`` may be an array, a
    list of arrays (multi-input), or a FeatureSet (then ``y=None``)."""
    if self._compiled is None:
        raise RuntimeError("call compile() before fit()")
    if isinstance(x, FeatureSet):
        return self._loop.fit_feature_set(x, batch_size=batch_size,
                                          nb_epoch=nb_epoch,
                                          validation_data=validation_data,
                                          rng=rng, callbacks=callbacks,
                                          end_trigger=end_trigger)
    return self._loop.fit(x, y, batch_size=batch_size, nb_epoch=nb_epoch,
                          validation_data=validation_data, shuffle=shuffle,
                          rng=rng, callbacks=callbacks, end_trigger=end_trigger)


def _evaluate(self: KerasNet, x, y=None, batch_size: int = 32):
    """``KerasNet.evaluate`` (``Topology.scala:496``)."""
    if self._compiled is None:
        raise RuntimeError("call compile() before evaluate()")
    if self.params is None:
        raise RuntimeError("no weights; fit() or init_weights() first")
    return self._loop.evaluate(x, y, batch_size=batch_size)


def _predict(self: KerasNet, x, batch_size: int = 32, distributed: bool = True):
    """``KerasNet.predict`` (``Topology.scala:343`` family)."""
    if self.params is None:
        raise RuntimeError("no weights; fit() or init_weights() first")
    if self._compiled is None:
        self._loop = TrainingLoop(self, optax.identity(), objectives.get_loss("mse"), [])
    return self._loop.predict(x, batch_size=batch_size)


def _predict_classes(self: KerasNet, x, batch_size: int = 32, zero_based: bool = True):
    """``predictClass`` (``Predictor.scala:210``)."""
    from ....utils.prediction import probs_to_classes
    probs = self.predict(x, batch_size=batch_size)
    return probs_to_classes(probs, zero_based=zero_based)


# state attributes
KerasNet.params = None
KerasNet.net_state = None
KerasNet.opt_state = None
KerasNet.finished_epochs = 0
KerasNet.finished_iterations = 0
KerasNet._loop = None
KerasNet._checkpoint = None
KerasNet._train_summary = None
KerasNet._val_summary = None
KerasNet._lr = None
#: what the last ``fit`` call cost and where (docs/guides/TRAINING.md)
KerasNet.last_fit_report = None

KerasNet.compile = _compile
KerasNet.init_weights = _init_weights
KerasNet.set_checkpoint = _set_checkpoint
KerasNet.set_tensorboard = _set_tensorboard
KerasNet.set_profile = _set_profile
KerasNet.get_train_summary = _get_train_summary
KerasNet.get_validation_summary = _get_validation_summary
KerasNet.fit = _fit
KerasNet.evaluate = _evaluate
KerasNet.predict = _predict
KerasNet.predict_classes = _predict_classes
