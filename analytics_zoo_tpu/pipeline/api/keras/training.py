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
from ....observability import default_registry, instrument_jit, span
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
    """Pad the batch dim to ``size`` by repeating the last row — ONE policy
    for both host (numpy) and device-resident (jax) arrays, so the
    device-cache fast path pads identically to the host path."""
    xs = _as_list(x)
    out = []
    for a in xs:
        xp = jnp if isinstance(a, jax.Array) else np
        a = a if isinstance(a, jax.Array) else np.asarray(a)
        pad = size - a.shape[0]
        if pad > 0:
            a = xp.concatenate([a, xp.repeat(a[-1:], pad, axis=0)], axis=0)
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


def _stack_batches(items):
    """Stack K ``(x, y)`` minibatches into one ``(K, batch, ...)`` chunk for
    the multi-step scan dispatch. ``None`` labels pass through."""
    return jax.tree.map(lambda *xs: np.stack([np.asarray(a) for a in xs], axis=0),
                        *items)


def _chunked(it, k: int):
    buf = []
    for item in it:
        buf.append(item)
        if len(buf) == k:
            yield _stack_batches(buf)
            buf = []
    if buf:
        yield _stack_batches(buf)


class _FullPassEveryEpoch(Trigger):
    """``EveryEpoch`` over a sliced dataset: fires only when the finished
    slice pass completes a FULL pass over all slices
    (``ZooTrigger.scala:53-58``: ``currentSlice % numSlice == 0``)."""

    def __init__(self, num_slices: int):
        self.num_slices = int(num_slices)

    def __call__(self, state: TrainLoopState) -> bool:
        return state.epoch_finished and state.epoch % self.num_slices == 0


def _fired_within(trigger: Optional[Trigger], state: TrainLoopState,
                  prev_iter: int) -> bool:
    """Whether a trigger fired at any step in ``(prev_iter, state.iteration]``.
    With fused dispatches the loop only observes chunk boundaries; interval
    triggers are checked over the whole window so a fire inside the chunk is
    not lost — it is acted on at the boundary, up to (window-1) steps late:
    K-1 for scan chunks, a whole epoch for device_cache (which warns when a
    SeveralIteration interval is finer than the epoch)."""
    if trigger is None:
        return False
    if isinstance(trigger, SeveralIteration):
        return state.iteration // trigger.interval > prev_iter // trigger.interval
    return trigger(state)


def _write_param_histograms(tb, params, epochs, iteration,
                            n_steps: int = 0) -> None:
    """Per-layer weight histograms when the TrainSummary's "Parameters"
    trigger fires for any epoch in ``epochs`` (reference:
    ``TrainSummary.setSummaryTrigger("Parameters", ...)`` +
    ``Summary.scala``'s histogram writer). Called only at boundaries where
    the params are host-visible; under fused-epoch dispatch that is the
    final epoch of a fused block, covering the whole block's epochs —
    ``n_steps`` (steps per epoch) reconstructs each covered epoch's own
    boundary iteration, ending at ``iteration``, and an iteration-based
    trigger is checked over that epoch's whole ``(boundary - n_steps,
    boundary]`` window (``_fired_within`` semantics: a fire landing
    mid-epoch is acted on at the boundary, not dropped)."""
    epochs = list(epochs)
    trig = getattr(tb, "parameters_trigger", None)
    if trig is not None:
        # Trigger-like form: evaluated per covered epoch (params are only
        # host-visible at the block end, but the *decision* must match
        # what per-epoch dispatch would have decided); without n_steps the
        # window degrades to the boundary iteration itself
        last = len(epochs) - 1
        window = max(n_steps, 1)
        if not any(_fired_within(
                trig,
                TrainLoopState(iteration=iteration - (last - k) * n_steps,
                               epoch=e, epoch_finished=True),
                prev_iter=iteration - (last - k) * n_steps - window)
                   for k, e in enumerate(epochs)):
            return
    else:
        freq = getattr(tb, "parameters_every_epochs", None)
        if not freq or not any(e % freq == 0 for e in epochs):
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
    input buffers, so any tree that outlives a step (``model.params``, the
    retry snapshot) must never alias one that enters the step.

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


class _SentinelMonitor:
    """Host-side bookkeeping for the packed per-step sentinel flags
    (``common/anomaly.py``; one int32 per step, ``(K,)`` per scan chunk).

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

    def push(self, first_iter: int, flags_dev) -> None:
        """Queue one dispatch's flag output (scalar or (K,) vector)."""
        shape = getattr(flags_dev, "shape", ())
        k = int(shape[0]) if shape else 1
        self.epoch_step_iters.extend(range(first_iter, first_iter + k))
        self.pending.append((first_iter, flags_dev))
        if len(self.pending) > self.LAG:
            self._drain_one()

    def note_replay_skip(self, k: int) -> None:
        """``k`` steps of a rollback replay were not re-dispatched (the
        offending data window) — counted as skipped, no loss recorded."""
        self.loop._m_skipped.inc(k)

    def drain(self) -> None:
        while self.pending:
            self._drain_one()

    def _drain_one(self) -> None:
        first_iter, flags_dev = self.pending.popleft()
        words = np.atleast_1d(np.asarray(flags_dev))
        for j, word in enumerate(words):
            f = int(word)
            self.epoch_flags.append(f)
            if f & anomaly.GRAD_CLIPPED:
                self.loop._m_clip.inc()
            kinds = anomaly.kinds_of(f)
            if not kinds:
                continue
            it = first_iter + j
            for kind in kinds:
                self.loop._m_anomaly[kind].inc()
            self.loop._registry.emit(
                "train.anomaly", iteration=it, epoch=self.epoch,
                kinds=",".join(kinds), mode=self.cfg.mode,
                action="skip" if self.cfg.mode == "recover" else "warn")
            if self.cfg.mode == "recover":
                self.loop._m_skipped.inc()
                self.loop._anomalous_steps.add(self.step_key(it))
                self.epoch_skips += 1
                log.warning(
                    "anomalous step at iteration %d (%s): update "
                    "discarded (%d/%d skips this epoch)", it,
                    ",".join(kinds), self.epoch_skips,
                    self.cfg.max_skips_per_epoch)
            else:
                log.warning(
                    "anomalous step at iteration %d (%s) — "
                    "zoo.train.sentinel=warn: update APPLIED", it,
                    ",".join(kinds))
        if (self.cfg.mode == "recover"
                and self.epoch_skips > self.cfg.max_skips_per_epoch):
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
    run once a fit or once an epoch follow one another in ``_fit_impl``:
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
        self._scan_step = None
        self._epoch_fns: Dict[Tuple, Any] = {}
        self._eval_step = None
        self._predict_step = None
        # device-resident copy of the latest FeatureSet (device_cache path)
        # — re-uploading per fit call costs a full host→device transfer of
        # the whole set. The entry HOLDS the fs object: a bare id() key
        # could be reused by a new FeatureSet after GC and silently serve
        # the old dataset's arrays.
        self._data_cache: Dict[Tuple, Any] = {}
        # observability (docs/guides/OBSERVABILITY.md): every fit updates
        # the zoo_train_* family in the process-wide registry
        self._registry = default_registry()
        self._m_step_time = self._registry.histogram(
            "zoo_train_step_seconds",
            "optimizer-step wall time (amortized over fused dispatches)")
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

    # -- goodput attribution -------------------------------------------------
    def _gp_note(self, category: str) -> None:
        """Attribute wall clock since the ledger's mark to ``category``
        (no-op outside an accounted fit)."""
        if self._goodput is not None:
            self._goodput.note(category)

    def _dispatch(self, fn, iteration: int, steps: int, *args):
        """Dispatch one segment of ``steps`` optimizer steps (a step, a scan
        chunk, a fused epoch) starting at ``iteration``: the in-flight
        probe sees the depth it finds and the loss it will fill, the
        profiler a ``zoo.train.step`` step event."""
        probe = self._probe
        probe.at_dispatch()
        with self._phases.dispatch, jax.profiler.StepTraceAnnotation(
                "zoo.train.step", step_num=iteration):
            out = fn(*args)
        # every step function returns (params, opt_state, net_state, loss)
        # or, under the sentinels, (..., sentinel state, loss, flags)
        probe.dispatched(out[-1] if len(out) == 4 else out[-2], steps)
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

    def _moe_report(self, before: Dict[str, Dict[str, Any]]
                    ) -> Optional[Dict[str, Any]]:
        """The routed layers' counters over the fit that just ended, read
        from the layer state once, after the fit's last drain (no readback
        a step), and published: ``zoo_moe_assignments_total{layer=,held=}``,
        ``zoo_moe_dropped_assignments_total``, and of the fit's last step
        ``zoo_moe_expert_tokens{layer=,expert=}`` and
        ``zoo_moe_load_max_over_mean{layer=}`` (largest held expert's
        tokens over the held experts' mean); what bounded the layers' work,
        ``zoo_moe_rows_run_total{layer=}``, ``zoo_moe_choice_passes_total
        {layer=}``, ``zoo_moe_chunk_runs_total{layer=,compact=}``, and in
        the report their ratios (``layers/moe.py::bound_ratios``). ``None``
        for a model without such layers."""
        from .layers.moe import (WIDE_COUNTERS, bound_ratios,
                                 routed_layer_totals)
        after = routed_layer_totals(self.model.net_state)
        if not after:
            return None
        reg = self._registry
        counts = tuple(WIDE_COUNTERS)
        report: Dict[str, Any] = {"layers": {}, **dict.fromkeys(counts, 0)}
        for name, now in after.items():
            was = before.get(name, {})
            layer = {k: now[k] - was.get(k, 0) for k in counts}
            for key in counts:
                report[key] += layer[key]
            layer.update(bound_ratios(layer))
            layer["expert_tokens"] = now["expert_tokens"]
            mean = sum(now["held_tokens"]) / max(len(now["held_tokens"]), 1)
            layer["load_max_over_mean"] = (
                max(now["held_tokens"]) / mean if mean > 0 else 0.0)
            report["layers"][name] = layer
            reg.counter(  # zoolint: disable=ZL015 one series a layer
                "zoo_moe_rows_run_total",
                "rows a RoutedExperts layer's row buffers held: the cut "
                "size C a chunk whose held assignments fit in it, tokens x "
                "top_k a chunk else",
                labels={"layer": name}).inc(layer["rows_run"])
            reg.counter(  # zoolint: disable=ZL015 one series a layer
                "zoo_moe_choice_passes_total",
                "gather-sum passes over a token's choices a RoutedExperts "
                "layer ran, a chunk: the most held choices any token had, "
                "or all top_k where those are many",
                labels={"layer": name}).inc(layer["choice_passes"])
            for cut, n in (("true", layer["compact_runs"]),
                           ("false", layer["chunk_runs"]
                            - layer["compact_runs"])):
                reg.counter(  # zoolint: disable=ZL015 one series a layer
                    "zoo_moe_chunk_runs_total",
                    "chunks of tokens a RoutedExperts layer ran, by whether "
                    "its row buffers were cut to the rows held (compact="
                    "true) or held the worst case",
                    labels={"layer": name, "compact": cut}).inc(n)
            for held, key in (("true", "held"), ("false", "absent")):
                reg.counter(  # zoolint: disable=ZL015 one series a layer
                    "zoo_moe_assignments_total",
                    "token-to-expert assignments routed by a RoutedExperts "
                    "layer: computed here (held=true) or left to absent "
                    "experts (held=false)",
                    labels={"layer": name, "held": held}).inc(layer[key])
            for e, n in enumerate(now["expert_tokens"]):
                reg.gauge(  # zoolint: disable=ZL015 bounded: router width
                    "zoo_moe_expert_tokens",
                    "assignments per router output in the last step of "
                    "the last fit",
                    labels={"layer": name, "expert": str(e)}).set(n)
            reg.gauge(  # zoolint: disable=ZL015 one series a layer
                "zoo_moe_load_max_over_mean",
                "largest held expert's tokens over the held experts' mean, "
                "last step of the last fit",
                labels={"layer": name}).set(layer["load_max_over_mean"])
        reg.counter(
            "zoo_moe_dropped_assignments_total",
            "assignments a RoutedExperts layer placed with no expert "
            "(0 by construction: the layer has no capacity)"
        ).inc(report["dropped"])
        report.update(bound_ratios(report))
        return report

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
        return {
            "wall_s": t_end - t_open,
            "steps": self._probe.steps,
            "ledger": (self._goodput.seconds()
                       if self._goodput is not None else {}),
            "host_s": {phase: v - host_before[phase]
                       for phase, v in self._phases.seconds().items()},
            "inflight": self._probe.summary(),
            "compile": compiled,
        }

    # -- jitted steps -------------------------------------------------------
    #: the labels of the most recent fused-CE gauge write in this process —
    #: a later non-fused (or differently-headed) loop zeroes the stale
    #: series so the scrape never claims fusion is active when it is not
    _last_fused_labels = None
    _FUSED_GAUGE_HELP = ("1 while the fused blockwise LM-head cross-entropy "
                         "is active for the current training loop")

    def _loss_application(self):
        """``fn(params, net_state, x, y, rng) -> (loss, new_state)`` — the
        forward+loss shared by every training-step builder. Resolves the
        fused LM-head cross-entropy (``fused_loss.resolve_fused_loss``,
        ``zoo.train.fused_ce``) ONCE per loop — the scan/epoch builders
        call this at trace time, and re-resolving would re-log and
        re-write the gauge on every retrace: a big-vocab Dense head with
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
        # loss, applied as trace-time scopes around every builder's
        # forward so existing models ride seq/pipe meshes unchanged
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
                return loss_fn(y, yp), ns
            self._apply_loss = apply_loss
            return apply_loss
        log.info("fused LM-head cross-entropy engaged: head=%s vocab=%d%s "
                 "(zoo.train.fused_ce; the (N, V) logits tensor is never "
                 "materialized)", spec.head.name, spec.head.output_dim,
                 " VOCAB-SHARDED over the model axis" if spec.sharded
                 else "")
        labels = {"head": spec.head.name,
                  "vocab": str(spec.head.output_dim),
                  "sharded": "1" if spec.sharded else "0"}
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
        saving them across the scan — 32k training can raise batch/K
        instead of sitting at batch 1. ``true``/``dots`` keeps MXU outputs
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
        (like the fused-loss resolution): every step builder of a loop
        must agree on the step signature, and with ``sentinel=off`` and
        no clipping the builders emit the historical step exactly —
        zero sentinel ops, bit-identical numerics."""
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
        """The per-step forward/backward/update shared by the single-step
        and scan builders. Returns ``(core_fn, cfg)``.

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
        opt = self.optimizer
        apply_loss = self._loss_application()
        remat = self._remat_wrapper()
        cfg = self._sentinel_config()

        def backward(params, net_state, x, y, rng):
            def lfn(p):
                l, ns = apply_loss(p, net_state, x, y, rng)
                aux = _aux_loss_sum(ns)
                return (l if aux is None else l + aux), ns
            return jax.value_and_grad(remat(lfn), has_aux=True)(params)

        if not cfg.active:
            def plain(params, opt_state, net_state, rng, x, y):
                (l, ns), grads = backward(params, net_state, x, y, rng)
                updates, opt_state = opt.update(grads, opt_state, params)
                opt_state = self._pin_opt_state(opt_state)
                params = self._pin_params(
                    optax.apply_updates(params, updates))
                return params, opt_state, ns, l
            return plain, cfg

        def guarded(params, opt_state, net_state, sstate, rng, fault, x, y):
            (l, ns), grads = backward(params, net_state, x, y, rng)
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
                bad = (flags & anomaly.ANOMALY_MASK) > 0

                def _apply(operand):
                    p, o, g, new_ns = operand
                    updates, new_opt = opt.update(g, o, p)
                    new_opt = self._pin_opt_state(new_opt)
                    return (self._pin_params(
                        optax.apply_updates(p, updates)), new_opt, new_ns)

                def _skip(operand):
                    p, o, _g, _new_ns = operand
                    return p, o, net_state

                params, opt_state, net_state = jax.lax.cond(
                    bad, _skip, _apply, (params, opt_state, grads, ns))
            else:
                updates, opt_state = opt.update(grads, opt_state, params)
                opt_state = self._pin_opt_state(opt_state)
                params = self._pin_params(
                    optax.apply_updates(params, updates))
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

    def _make_scan_body(self, base_rng):
        """The shared per-step scan body (fold_in rng schedule → grad →
        optimizer update) used by both the K-step chunk dispatch and the
        whole-epoch dispatch, so the two fused paths can never diverge
        numerically from each other or from the single-step path."""
        core, cfg = self._make_step_core()

        if not cfg.active:
            def body(carry, batch):
                params, opt_state, net_state, i = carry
                x, y = batch
                rng = jax.random.fold_in(base_rng, i)
                params, opt_state, ns, l = core(params, opt_state,
                                                net_state, rng, x, y)
                return (params, opt_state, ns, i + 1), l
            return body

        def body(carry, batch):
            params, opt_state, net_state, sstate, i = carry
            x, y, fault = batch
            rng = jax.random.fold_in(base_rng, i)
            params, opt_state, ns, sstate, l, flags = core(
                params, opt_state, net_state, sstate, rng, fault, x, y)
            return (params, opt_state, ns, sstate, i + 1), (l, flags)
        return body

    def build_scan_step(self):
        """Multi-step train function: runs K optimizer steps per dispatch via
        ``lax.scan`` over stacked batches of shape ``(K, batch, ...)``.

        This is the TPU-idiomatic answer to the reference's
        one-Spark-job-per-iteration scheduling overhead
        (``wp-bigdl.md:171-173``: >10% of compute lost to task dispatch at
        scale): here the per-step Python/runtime dispatch cost is amortized
        K-fold, leaving XLA a single fused program per chunk. With the
        sentinel layer active the chunk additionally carries the EWMA
        state and returns a ``(K,)`` packed flag vector alongside the
        ``(K,)`` losses — one readback, per-step granularity."""
        cfg = self._sentinel_config()

        if not cfg.active:
            def chunk(params, opt_state, net_state, base_rng, iter0, xs, ys):
                (params, opt_state, net_state, _), losses = jax.lax.scan(
                    self._make_scan_body(base_rng),
                    (params, opt_state, net_state, iter0), (xs, ys))
                return params, opt_state, net_state, losses
        else:
            def chunk(params, opt_state, net_state, sstate, base_rng,
                      iter0, xs, ys, fault):
                (params, opt_state, net_state, sstate, _), \
                    (losses, flags) = jax.lax.scan(
                        self._make_scan_body(base_rng),
                        (params, opt_state, net_state, sstate, iter0),
                        (xs, ys, fault))
                return params, opt_state, net_state, sstate, losses, flags

        self._scan_step = instrument_jit(chunk, name="train.scan_chunk",
                                         registry=self._registry,
                                         donate_argnums=(0, 1, 2))
        return self._scan_step

    def _shard_opt_state(self, opt_state, psh, repl):
        """Committed placement for optimizer state: param-shaped leaves
        (adam moments) follow the param shardings, counters and the like
        replicate. Used for BOTH fresh and reused state so every fit call
        presents identical input shardings to the jitted step — otherwise
        the first call hands uncommitted counters while later calls hand
        committed ones, and each fit() misses the jit cache and recompiles
        the whole epoch program (~20 s on a real chip).

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
        across scan iterations, and moments on their params' shardings
        where those are pinned (no-op otherwise)."""
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

    def build_epoch_fn(self, n: int, batch_size: int, n_steps: int,
                       shuffle: bool = True):
        """Whole-epoch train function over a device-resident dataset
        (``zoo.train.device_cache``): shuffle (jax.random.permutation) and all
        ``n_steps`` optimizer steps run in ONE dispatch, so per-step host and
        dispatch latency vanish entirely.

        This is the HBM analogue of ``CachedDistributedFeatureSet``
        (``FeatureSet.scala:222-322``): the reference caches the dataset in
        executor RAM and re-shuffles an index per epoch; here the cache lives
        in device HBM and the re-shuffle is an on-device gather. The epoch's
        shuffled view is re-laid-out once per epoch under the stacked batch
        sharding, so the per-step scan body stays identical to the chunked
        path (numerically the same rng schedule as well)."""
        if self._sentinel_config().active:
            raise RuntimeError(
                "whole-epoch dispatch is unavailable with the anomaly-"
                "sentinel/grad-clip layer active (zoo.train.sentinel / "
                "zoo.train.grad_clip) — fit falls back to the streamed "
                "path automatically")
        key = (n, batch_size, n_steps, shuffle)
        if key in self._epoch_fns:
            return self._epoch_fns[key]
        body = self._make_epoch_body(n, batch_size, n_steps, shuffle)

        def epoch(params, opt_state, net_state, base_rng, iter0, shuffle_rng,
                  xs, ys):
            (params, opt_state, net_state, _), losses = body(
                (params, opt_state, net_state, iter0), base_rng, shuffle_rng,
                xs, ys)
            return params, opt_state, net_state, losses

        fn = instrument_jit(epoch, name="train.epoch",
                            registry=self._registry,
                            donate_argnums=(0, 1, 2))
        self._epoch_fns[key] = fn
        return fn

    def _make_epoch_body(self, n, batch_size, n_steps, shuffle):
        """The shared whole-epoch body (on-device shuffle gather → scan of
        optimizer steps) behind BOTH the per-epoch and the fused-epoch
        dispatch, so the two paths cannot diverge numerically."""
        stacked = mesh_lib.stacked_batch_sharding(self.mesh)
        n_used = n_steps * batch_size

        def body(carry, base_rng, shuffle_rng, xs, ys):
            params, opt_state, net_state, it = carry
            if shuffle:
                perm = jax.random.permutation(shuffle_rng, n)[:n_used]
            else:
                perm = jnp.arange(n_used)

            def shuffled(a):
                out = jnp.take(a, perm, axis=0).reshape(
                    (n_steps, batch_size) + a.shape[1:])
                return jax.lax.with_sharding_constraint(out, stacked)

            return jax.lax.scan(
                self._make_scan_body(base_rng),
                (params, opt_state, net_state, it),
                (jax.tree.map(shuffled, xs), jax.tree.map(shuffled, ys)))

        return body

    def build_multi_epoch_fn(self, n: int, batch_size: int, n_steps: int,
                             shuffle: bool, n_epochs: int):
        """``zoo.train.fuse_epochs``: K whole epochs (shuffle + steps) in ONE
        dispatch — a ``lax.scan`` over per-epoch shuffle keys around the
        epoch body. The per-epoch dispatch + loss-readback round trips are
        the remaining host cost after ``device_cache``; this amortizes
        them K-fold (what that buys on a directly attached chip: not
        re-measured). The rng schedule is
        identical to the per-epoch path, so losses match bit-for-bit."""
        if self._sentinel_config().active:
            raise RuntimeError(
                "fused-epoch dispatch is unavailable with the anomaly-"
                "sentinel/grad-clip layer active (zoo.train.sentinel / "
                "zoo.train.grad_clip)")
        key = (n, batch_size, n_steps, shuffle, n_epochs)
        if key in self._epoch_fns:
            return self._epoch_fns[key]
        body = self._make_epoch_body(n, batch_size, n_steps, shuffle)

        def multi(params, opt_state, net_state, base_rng, iter0,
                  shuffle_rngs, xs, ys):
            def one_epoch(carry, ep_rng):
                return body(carry, base_rng, ep_rng, xs, ys)

            (params, opt_state, net_state, _), L = jax.lax.scan(
                one_epoch, (params, opt_state, net_state, iter0),
                shuffle_rngs)
            return params, opt_state, net_state, L  # (n_epochs, n_steps)

        fn = instrument_jit(multi, name="train.multi_epoch",
                            registry=self._registry,
                            donate_argnums=(0, 1, 2))
        self._epoch_fns[key] = fn
        return fn

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
    def _maybe_compute_flops(self, fn, args, examples_per_dispatch) -> float:
        """One-shot XLA cost-analysis pass caching FLOPs/example for the MFU
        gauge. Opt-in (``zoo.metrics.flops``): the extra ``lower().compile()``
        costs a compile, wasted on backends with no known peak — and
        ``lower`` only reads avals/shardings, so calling it on buffers the
        subsequent dispatch donates is safe. Returns the seconds spent so
        callers can exclude the compile from their epoch-timing window
        (the metrics this pass feeds must not be skewed by it)."""
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
        try:
            flops = profiling.compiled_flops(fn.lower(*args).compile())
        except Exception:   # backend-dependent; never fail a fit for MFU
            flops = None
        # 0.0 latches "tried and unavailable" so the compile isn't retried
        self._flops_per_example = (
            flops / examples_per_dispatch if flops else 0.0)
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

    def _save_checkpoint(self, mgr: CheckpointManager, loop_state, params,
                         opt_state, net_state, sync: bool = False) -> None:
        """Cut a snapshot. Async by default: the step path pays one host
        transfer and the serialization/commit rides the manager's writer
        thread; ``sync=True`` (the SIGTERM path) blocks until committed."""
        mgr.save(loop_state.iteration,
                 {"params": params, "opt_state": opt_state,
                  "net_state": net_state},
                 meta={"epoch": loop_state.epoch,
                       "iteration": loop_state.iteration,
                       "epoch_finished": loop_state.epoch_finished},
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

    def _maybe_preempt(self, mgr, loop_state, params, opt_state,
                       net_state) -> None:
        """SIGTERM arrived (``zoo.checkpoint.on_sigterm``): cut one final
        SYNCHRONOUS checkpoint at this step boundary, publish in-memory
        state, and exit cleanly via :class:`TrainingPreempted`."""
        if mgr is None or not self._preempted.is_set():
            return
        log.warning("SIGTERM: cutting a final synchronous checkpoint at "
                    "iteration %d before exiting", loop_state.iteration)
        try:
            self._save_checkpoint(mgr, loop_state, params, opt_state,
                                  net_state, sync=True)
        except Exception:
            # the process is going down either way; the newest previous
            # snapshot (already committed) remains the resume point
            log.exception("final preemption checkpoint failed")
        model = self.model
        model.params, model.net_state, model.opt_state = _clone_tree(
            (params, net_state, opt_state))
        model.finished_iterations = loop_state.iteration
        raise TrainingPreempted(
            f"training preempted by SIGTERM; final checkpoint cut at "
            f"iteration {loop_state.iteration}")

    def _on_sigterm(self, signum, frame) -> None:
        grace = self._sigterm_grace
        if grace is not None:
            self._try_grace_cut(grace)      # raises when it cuts
        log.warning("SIGTERM received; requesting a final checkpoint at "
                    "the next step boundary")
        self._preempted.set()

    # -- SIGTERM grace budget (zoo.checkpoint.sigterm_grace_s) --------------
    def _segment_begin(self, mgr, loop_state, params, opt_state,
                       net_state) -> None:
        """A dispatch segment (one step / scan chunk / fused epoch) is
        about to enter the device. When the running duration estimate
        already exceeds the grace budget, clone the boundary state NOW —
        the dispatch donates these trees, so by the time the handler
        fires mid-segment the originals are deleted device buffers. A
        segment estimated to finish within the budget skips the clone
        (the handler just waits for the boundary), so the copy is only
        paid in the slow-segment regime it exists for."""
        if self._sigterm_grace is None or mgr is None:
            return
        est = self._segment_est
        if est is not None and est > self._sigterm_grace:
            self._boundary_ref = (
                mgr, loop_state.iteration, loop_state.epoch,
                loop_state.epoch_finished,
                _clone_tree((params, opt_state, net_state)))
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
        model = self.model
        model.params, model.net_state, model.opt_state = _clone_tree(
            (params, net_state, opt_state))
        model.finished_iterations = iteration
        raise TrainingPreempted(
            f"training preempted by SIGTERM; grace budget {grace:g}s is "
            f"shorter than the ~{eta:.2f}s to the next step boundary — "
            f"mid-epoch checkpoint cut at iteration {iteration}")

    def _fault_input(self) -> np.ndarray:
        """Host-side ``train.grads`` fault scheduling: one site call per
        dispatched optimizer step. Returns the ``[code, scale]`` pair the
        compiled step consumes (``anomaly.inject_grads``) — zeros (the
        shared no-fault constant) unless an active plan fires a
        nan_loss/nan_grad/spike spec at this call index."""
        spec = faults.inject("train.grads")
        if spec is None:
            return _NO_FAULT
        code = anomaly.FAULT_CODES.get(spec.kind)
        if code is None:        # e.g. a latency spec: already applied
            return _NO_FAULT
        return np.asarray([code, spec.scale], np.float32)

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
        from .layers.moe import routed_layer_totals
        moe_before = routed_layer_totals(self.model.net_state)
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
                moe = self._moe_report(moe_before)
            except Exception:   # accounting must not mask the fit's own error
                log.exception("routed-layer counters could not be read")
                moe = None
            if moe is not None:
                self.model.last_fit_report["moe"] = moe
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
        ctx = get_zoo_context()
        model = self.model
        self._phases.switch("fit.enter")
        # fail NOW, not after an epoch of compute: scan fusing stacks K
        # consecutive batches into one array (can't mix widths), and
        # validation/evaluate need one dense array
        if (getattr(fs, "ragged", False)
                and int(ctx.get("zoo.train.scan_steps", 1)) > 1):
            raise ValueError(
                "bucketed (ragged) datasets cannot use "
                "zoo.train.scan_steps > 1 — fused chunks stack same-shape "
                "batches; set scan_steps=1")
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

        # K>1 runs K optimizer steps per dispatch via lax.scan
        # (zoo.train.scan_steps); triggers are then observed at chunk
        # boundaries (see _fired_within)
        scan_steps = max(1, int(ctx.get("zoo.train.scan_steps", 1)))

        # anomaly sentinels (docs/guides/TRAINING.md): resolved once per
        # loop; active ⇒ the steps carry EWMA state + packed flags and
        # the host runs a lagged flag monitor
        sen = self._sentinel_config()
        monitor = _SentinelMonitor(self, sen) if sen.active else None

        if model.params is None:
            model.init_weights(rng=rng, sample_input=fs.sample(1))
        if not model.net_state:
            # weights installed by hand and the state reset: the layers
            # that keep state start from their own (none: {} again), so
            # that the step sees one state structure from its first call
            model.net_state = model.initial_state()
        if scan_steps > 1 and self._scan_step is None:
            self.build_scan_step()
        if self._train_step is None:
            self.build_train_step()

        repl = mesh_lib.replicated_sharding(self.mesh)
        # params: replicated under pure DP; sharded over the model axis when
        # the mesh has one (layers declare the specs — SURVEY §2.4 TP)
        psh = mesh_lib.param_shardings(model, model.params, self.mesh)
        self._param_shardings = psh if any(
            not s.is_fully_replicated for s in jax.tree.leaves(psh)) else None
        # clone: the donated train step must own its buffers exclusively —
        # without the copy, device_put of an already-replicated model.params
        # is a no-op alias and step 1 would delete the model's weights
        params = jax.device_put(_clone_tree(model.params), psh)
        net_state = jax.device_put(_clone_tree(model.net_state), repl)
        # eval_shape: the CURRENT optimizer's state structure, zero allocation
        fresh_struct = jax.tree_util.tree_structure(
            jax.eval_shape(self.optimizer.init, params))
        if model.opt_state is not None:
            # reuse stored optimizer state only when it structurally matches
            # the CURRENT optimizer — a clipping/optimizer change between
            # train calls (Estimator.scala:75-100) alters the optax state
            # tree, and feeding the old one would corrupt the update
            same = (jax.tree_util.tree_structure(model.opt_state)
                    == fresh_struct)
            if same:
                opt_state = self._shard_opt_state(
                    _clone_tree(model.opt_state), psh, repl)
            else:
                log.warning("optimizer structure changed since the last fit; "
                            "resetting optimizer state")
                opt_state = self._shard_opt_state(
                    self.optimizer.init(params), psh, repl)
        else:
            opt_state = self._shard_opt_state(self.optimizer.init(params),
                                              psh, repl)

        # resume: if a checkpoint directory is configured and holds a snapshot
        # newer than this model's progress, restore it (process-death resume)
        mgr = self._ckpt_manager()
        # registered so _fit_with_retry can join/close the async writer on
        # every exit path (including exceptions and preemption)
        self._active_ckpt_mgr = mgr
        ckpt_trigger = self._ckpt_trigger()
        if mgr is not None:
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
        # sliced disk tier: one loop "epoch" is ONE slice pass; nb_epoch and
        # EveryEpoch-style triggers count FULL passes of num_of_slice slices
        # (DiskFeatureSet + ZooTrigger.scala:44-66 slice awareness)
        n_slices = int(getattr(fs, "num_of_slice", 1) or 1)
        if n_slices > 1:
            def slice_aware(trig):
                if isinstance(trig, EveryEpoch):
                    return _FullPassEveryEpoch(n_slices)
                if isinstance(trig, MaxEpoch):
                    return MaxEpoch(trig.max_epoch * n_slices)
                if trig is not None and not isinstance(
                        trig, (SeveralIteration, _FullPassEveryEpoch)):
                    log.warning("trigger %s under a %d-slice DiskFeatureSet "
                                "observes SLICE passes as epochs, not full "
                                "passes", type(trig).__name__, n_slices)
                return trig
            ckpt_trigger = slice_aware(ckpt_trigger)
            end_trigger = slice_aware(end_trigger)
        if "target" not in target_holder:
            # "train nb_epoch more" counts from post-resume progress, matching
            # the reference's getFinishedEpoch continuation (Topology.scala:373-386)
            target_holder["target"] = (model.finished_epochs
                                       + nb_epoch * n_slices)
        target_epoch = target_holder["target"]

        # device-cache fast path: dataset lives in HBM, one dispatch per epoch
        device_cache = bool(ctx.get("zoo.train.device_cache", False))
        if device_cache and sen.active:
            # sentinels observe per-step flags at dispatch boundaries and
            # recovery needs the host in the loop; a whole-epoch dispatch
            # would defer both to epoch granularity — fall back to the
            # streamed path (documented in TRAINING.md)
            log.warning(
                "zoo.train.device_cache disabled for this fit: the "
                "anomaly-sentinel/grad-clip layer is active "
                "(zoo.train.sentinel=%s, zoo.train.grad_clip=%g); using "
                "the streamed dispatch path", sen.mode, sen.grad_clip)
            device_cache = False
        epoch_fn = None
        xs_dev = ys_dev = None
        # n_slices first: DiskFeatureSet.y is a property that would gather
        # the whole label file just to answer the None check
        if (device_cache and n_slices <= 1
                and getattr(fs, "device_cacheable", True)
                and fs.y is not None):
            n_steps = fs.steps_per_epoch(batch_size, drop_last=True)
            for trig, role in ((ckpt_trigger, "checkpoint"),
                               (end_trigger, "end")):
                if (isinstance(trig, SeveralIteration)
                        and trig.interval < n_steps):
                    log.warning(
                        "device_cache runs one dispatch per epoch, so the %s "
                        "trigger SeveralIteration(%d) is only observed at "
                        "epoch boundaries (%d steps) — up to %d steps late",
                        role, trig.interval, n_steps,
                        n_steps - trig.interval)
            # the shuffled gather only reads indices < len(fs), so padding
            # rows (needed to make the leading dim shardable over dp) are
            # never selected
            n_padded = _round_up(len(fs), dp)

            def put(a):
                # device-resident inputs (extract→fit chain) pad and
                # relayout ON DEVICE — no host round trip
                return jax.device_put(jnp.asarray(_pad_to(a, n_padded)),
                                      mesh_lib.batch_sharding(self.mesh))

            epoch_fn = self.build_epoch_fn(len(fs), batch_size, n_steps,
                                           shuffle=fs.shuffle)
            cache_key = (id(fs), len(fs), n_padded)
            if cache_key not in self._data_cache:
                # keep only the latest dataset resident (HBM is the scarce
                # resource; switching sets back and forth re-uploads)
                self._data_cache.clear()
                self._data_cache[cache_key] = (fs, jax.tree.map(put, fs.x),
                                               jax.tree.map(put, fs.y))
            _, xs_dev, ys_dev = self._data_cache[cache_key]

        base_rng = rng if rng is not None else ctx.rng()
        throttle_cpu = jax.default_backend() == "cpu"
        # sentinel EWMA carry (device scalars) — fresh per fit attempt:
        # after a rollback the restored params' gradient scale is the
        # baseline worth learning, not the diverging run's
        sstate = anomaly.init_state() if sen.active else None
        # the no-fault input for scan chunks, allocated ONCE per fit and
        # sliced per dispatch (the single-step path shares _NO_FAULT)
        no_fault_chunk = (np.zeros((scan_steps, 2), np.float32)
                          if sen.active and scan_steps > 1 else None)
        history: Dict[str, List[float]] = {"loss": []}
        loop_state = TrainLoopState(iteration=model.finished_iterations,
                                    epoch=model.finished_epochs + 1)
        stop = False

        # fused-epoch fast path: K epochs per dispatch. Only when nothing
        # needs the host between epochs — no checkpointing, validation, or
        # end trigger (nb_epoch still bounds the run); per-epoch losses and
        # records come out identical to the per-epoch path (same rng
        # schedule), only the wall timing is amortized across the block.
        fuse = int(ctx.get("zoo.train.fuse_epochs", 1))
        if (epoch_fn is not None and fuse > 1 and mgr is None
                and validation_data is None and end_trigger is None):
            n_steps = fs.steps_per_epoch(batch_size, drop_last=True)
            tb = getattr(model, "_train_summary", None)
            epoch = model.finished_epochs
            while epoch < target_epoch:
                self._phases.switch()
                g = min(fuse, target_epoch - epoch)
                t0 = time.time()
                it0 = jnp.asarray(loop_state.iteration, jnp.int32)
                if g == 1:
                    shuffle_rng = jax.random.key(
                        fs.seed + ctx.seed + epoch + 1)
                    t0 += self._maybe_compute_flops(
                        epoch_fn, (params, opt_state, net_state, base_rng,
                                   it0, shuffle_rng, xs_dev, ys_dev),
                        n_steps * batch_size)
                    params, opt_state, net_state, L = self._dispatch(
                        epoch_fn, loop_state.iteration, n_steps,
                        params, opt_state, net_state, base_rng, it0,
                        shuffle_rng, xs_dev, ys_dev)
                else:
                    mfn = self.build_multi_epoch_fn(
                        len(fs), batch_size, n_steps, fs.shuffle, g)
                    keys = jnp.stack(
                        [jax.random.key(fs.seed + ctx.seed + e)
                         for e in range(epoch + 1, epoch + g + 1)])
                    t0 += self._maybe_compute_flops(
                        mfn, (params, opt_state, net_state, base_rng, it0,
                              keys, xs_dev, ys_dev),
                        g * n_steps * batch_size)
                    params, opt_state, net_state, L = self._dispatch(
                        mfn, loop_state.iteration, g * n_steps,
                        params, opt_state, net_state, base_rng, it0, keys,
                        xs_dev, ys_dev)
                self._drain([L], reduce=False)
                self._phases.switch("epoch.tail")
                L = np.asarray(L).reshape(g, -1)
                dt = (time.time() - t0) / g
                self._phases.switch("epoch.publish")
                self._observe_fit_metrics(g * n_steps, dt * g,
                                          g * n_steps * batch_size)
                loop_state.iteration += g * n_steps
                # publish once per block: the intermediate epochs' params
                # never materialize on the host (that is the point)
                model.params, model.net_state, model.opt_state = _clone_tree(
                    (params, net_state, opt_state))
                model.finished_iterations = loop_state.iteration
                thr = (n_steps * batch_size / dt) if dt > 0 else 0.0
                lr = getattr(model, "_lr", None)
                # every epoch inside a fused block completes by construction
                loop_state.epoch_finished = True
                for j in range(g):
                    e = epoch + 1 + j
                    last = j == g - 1
                    epoch_loss = float(L[j].mean())
                    history["loss"].append(epoch_loss)
                    model.finished_epochs = e
                    loop_state.epoch = e
                    it_e = loop_state.iteration - (g - 1 - j) * n_steps
                    # intermediate epochs' weights never materialize on the
                    # host (that is the point of fusing) — their records say
                    # so with None rather than smuggling end-of-block params
                    # under an earlier epoch number
                    record = {"epoch": e, "loss": epoch_loss,
                              "iteration": it_e, "throughput": thr,
                              "params": model.params if last else None,
                              "opt_state": model.opt_state if last else None,
                              "net_state": model.net_state if last else None,
                              "loop_state": loop_state}
                    if tb is not None:
                        for k2, lv in enumerate(L[j]):
                            tb.add_scalar("Loss", float(lv),
                                          it_e - n_steps + k2 + 1)
                        tb.add_scalar("Throughput", thr, it_e)
                        if callable(lr):
                            tb.add_scalar("LearningRate", float(lr(it_e)),
                                          it_e)
                        elif isinstance(lr, (int, float)):
                            tb.add_scalar("LearningRate", float(lr), it_e)
                        if last:
                            _write_param_histograms(
                                tb, model.params,
                                range(epoch + 1, epoch + g + 1), it_e,
                                n_steps=n_steps)
                        tb.writer.flush()
                    log.info("Epoch %d: loss=%.6f (%.1f ex/s)", e,
                             epoch_loss, thr)
                    for cb in callbacks:
                        cb(record)
                epoch += g
            return history

        epoch = model.finished_epochs  # so nb_epoch=0 is a clean no-op
        for epoch in range(model.finished_epochs + 1, target_epoch + 1):
            # epoch-boundary overhead (metrics, callbacks, validation of
            # the previous epoch) since the last step lands on idle: the
            # previous epoch's tail drained the device
            self._gp_note("idle")
            self._phases.switch()       # fit.enter or epoch.publish ends
            t0 = time.time()
            losses = []
            n_seen = 0
            loop_state.epoch = epoch
            # clear the boundary flag: mid-epoch trigger checks must not see
            # the previous epoch's True (stale EveryEpoch/MaxEpoch fires)
            loop_state.epoch_finished = False
            if monitor is not None:
                monitor.begin_epoch(epoch, loop_state.iteration)
            if epoch_fn is not None:
                prev_iter = loop_state.iteration
                shuffle_rng = jax.random.key(fs.seed + ctx.seed + epoch)
                it0 = jnp.asarray(prev_iter, jnp.int32)
                n_steps = fs.steps_per_epoch(batch_size, drop_last=True)
                t0 += self._maybe_compute_flops(
                    epoch_fn, (params, opt_state, net_state, base_rng, it0,
                               shuffle_rng, xs_dev, ys_dev),
                    n_steps * batch_size)
                self._segment_begin(mgr, loop_state, params, opt_state,
                                    net_state)
                params, opt_state, net_state, l = self._dispatch(
                    epoch_fn, prev_iter, n_steps,
                    params, opt_state, net_state, base_rng, it0, shuffle_rng,
                    xs_dev, ys_dev)
                self._segment_end()
                self._gp_note("device_step")   # whole-epoch dispatch
                losses.append(l)
                loop_state.iteration += n_steps
                n_seen += n_steps * batch_size
                if mgr is not None and _fired_within(ckpt_trigger, loop_state,
                                                     prev_iter):
                    self._save_checkpoint(mgr, loop_state, params, opt_state,
                                          net_state)
                self._maybe_preempt(mgr, loop_state, params, opt_state,
                                    net_state)
                if _fired_within(end_trigger, loop_state, prev_iter):
                    stop = True
                stream = ()
            elif scan_steps > 1:
                batches = fs.iter_batches(batch_size, epoch=ctx.seed + epoch,
                                          drop_last=True)
                stream = prefetch_to_device(
                    _chunked(batches, scan_steps), self.mesh,
                    sharding=mesh_lib.stacked_batch_sharding(self.mesh),
                    ledger=self._goodput, phases=self._phases)
            else:
                batches = fs.iter_batches(batch_size, epoch=ctx.seed + epoch,
                                          drop_last=True)
                stream = prefetch_to_device(batches, self.mesh,
                                            ledger=self._goodput,
                                            phases=self._phases)
            for bx_d, by_d in stream:
                prev_iter = loop_state.iteration
                k = jax.tree.leaves(bx_d)[0].shape[0] if scan_steps > 1 \
                    else 1
                if (monitor is not None and self._anomalous_steps
                        and any(monitor.step_key(prev_iter + j)
                                in self._anomalous_steps
                                for j in range(k))):
                    # rollback replay: the offending data window is NOT
                    # re-dispatched (its steps were flagged before the
                    # rollback); iteration still advances so the rng
                    # schedule and trigger windows stay aligned with the
                    # original attempt
                    loop_state.iteration += k
                    monitor.note_replay_skip(k)
                    self._gp_note("anomaly_skip")
                    if mgr is not None and _fired_within(
                            ckpt_trigger, loop_state, prev_iter):
                        self._save_checkpoint(mgr, loop_state, params,
                                              opt_state, net_state)
                    self._maybe_preempt(mgr, loop_state, params, opt_state,
                                        net_state)
                    if _fired_within(end_trigger, loop_state, prev_iter):
                        stop = True
                        break
                    continue
                if scan_steps > 1:
                    it0 = jnp.asarray(prev_iter, jnp.int32)
                    if monitor is None:
                        t0 += self._maybe_compute_flops(
                            self._scan_step,
                            (params, opt_state, net_state, base_rng, it0,
                             bx_d, by_d), k * batch_size)
                        self._segment_begin(mgr, loop_state, params,
                                            opt_state, net_state)
                        params, opt_state, net_state, l = self._dispatch(
                            self._scan_step, prev_iter, k,
                            params, opt_state, net_state, base_rng, it0,
                            bx_d, by_d)
                        self._segment_end()
                    else:
                        fault = (np.stack([self._fault_input()
                                           for _ in range(k)])
                                 if sen.faults
                                 else no_fault_chunk[:k])
                        t0 += self._maybe_compute_flops(
                            self._scan_step,
                            (params, opt_state, net_state, sstate,
                             base_rng, it0, bx_d, by_d, fault),
                            k * batch_size)
                        self._segment_begin(mgr, loop_state, params,
                                            opt_state, net_state)
                        (params, opt_state, net_state, sstate, l,
                         flags) = self._dispatch(
                             self._scan_step, prev_iter, k,
                             params, opt_state, net_state, sstate,
                             base_rng, it0, bx_d, by_d, fault)
                        self._segment_end()
                        monitor.push(prev_iter, flags)
                    loop_state.iteration += k
                    n_seen += k * batch_size
                else:
                    step_rng = jax.random.fold_in(base_rng, prev_iter)
                    if monitor is None:
                        t0 += self._maybe_compute_flops(
                            self._train_step,
                            (params, opt_state, net_state, step_rng, bx_d,
                             by_d), batch_size)
                        self._segment_begin(mgr, loop_state, params,
                                            opt_state, net_state)
                        params, opt_state, net_state, l = self._dispatch(
                            self._train_step, prev_iter, 1,
                            params, opt_state, net_state, step_rng, bx_d,
                            by_d)
                        self._segment_end()
                    else:
                        fault = (self._fault_input() if sen.faults
                                 else _NO_FAULT)
                        t0 += self._maybe_compute_flops(
                            self._train_step,
                            (params, opt_state, net_state, sstate,
                             step_rng, fault, bx_d, by_d), batch_size)
                        self._segment_begin(mgr, loop_state, params,
                                            opt_state, net_state)
                        (params, opt_state, net_state, sstate, l,
                         flags) = self._dispatch(
                             self._train_step, prev_iter, 1,
                             params, opt_state, net_state, sstate,
                             step_rng, fault, bx_d, by_d)
                        self._segment_end()
                        monitor.push(prev_iter, flags)
                    loop_state.iteration += 1
                    n_seen += batch_size
                losses.append(l)
                # XLA:CPU only — bound host run-ahead. Its in-process
                # collective rendezvous aborts (40 s timeout) when dozens
                # of slow queued programs starve some device threads;
                # blocking every few dispatches caps the queue. Real TPU
                # runtimes pipeline deeply and stay unthrottled.
                if throttle_cpu and len(losses) % 4 == 0:
                    jax.block_until_ready(l)
                if mgr is not None and _fired_within(ckpt_trigger, loop_state,
                                                     prev_iter):
                    self._save_checkpoint(mgr, loop_state, params, opt_state,
                                          net_state)
                self._maybe_preempt(mgr, loop_state, params, opt_state,
                                    net_state)
                if _fired_within(end_trigger, loop_state, prev_iter):
                    stop = True
                    break
            completed = not stop  # stop=True means the epoch was cut short
            if stop and stream:
                # a mid-epoch stop leaves the pipeline suspended at its
                # yield: end it here, and with it its last ledger interval
                # (left to the collector, it would close after the tail)
                stream.close()
            mean_loss = self._drain(losses, reduce=monitor is None)
            self._phases.switch("epoch.tail")
            if monitor is not None:
                # drain every pending flag first (escalation may raise
                # here, BEFORE the boundary checkpoint below); in recover
                # mode skipped steps' losses were never applied and are
                # excluded from the epoch mean
                lv = (np.concatenate([np.atleast_1d(np.asarray(l))
                                      for l in losses])
                      if losses else np.zeros(0, np.float32))
                lmask = monitor.loss_mask(len(lv))
                epoch_loss = (float(lv[lmask].mean()) if lmask.any()
                              else float("nan"))
            else:
                epoch_loss = (float(mean_loss) if losses else float("nan"))
            dt = time.time() - t0
            self._phases.switch("epoch.publish")
            self._observe_fit_metrics(n_seen // batch_size, dt, n_seen)
            history["loss"].append(epoch_loss)
            loop_state.epoch_finished = completed
            if hasattr(end_trigger, "record"):
                end_trigger.record(epoch_loss)
            # cut a snapshot at the trigger, or unconditionally on a mid-epoch
            # stop so the truncated epoch's progress survives (its meta says
            # epoch_finished=False, so a resume retrains that epoch)
            if mgr is not None and (stop or ckpt_trigger(loop_state)):
                self._save_checkpoint(mgr, loop_state, params, opt_state,
                                      net_state)

            # publish progress every epoch — clones, because the live trees
            # feed the donating train step next epoch; this is also what a
            # retry attempt falls back to when the newest snapshot is older
            model.params, model.net_state, model.opt_state = \
                _clone_tree((params, net_state, opt_state))
            if completed:
                model.finished_epochs = epoch
            model.finished_iterations = loop_state.iteration

            record = {"epoch": epoch, "loss": epoch_loss,
                      "iteration": loop_state.iteration,
                      "throughput": n_seen / dt if dt > 0 else 0.0,
                      "params": model.params, "opt_state": model.opt_state,
                      "net_state": model.net_state, "loop_state": loop_state}
            val = None
            if validation_data is not None:
                if isinstance(validation_data, FeatureSet):
                    vx, vy = validation_data.x, validation_data.y
                else:
                    vx, vy = validation_data
                val = self.evaluate(vx, vy, batch_size=batch_size)
                for k, v in val.items():
                    history.setdefault("val_" + k, []).append(v)
                record.update({"val_" + k: v for k, v in val.items()})
            tb = getattr(model, "_train_summary", None)
            if tb is not None:
                # one Loss point per optimizer step (the reference's
                # per-iteration granularity), written at epoch end so no
                # device sync lands inside the dispatch pipeline
                loss_vec = (np.concatenate(
                    [np.atleast_1d(np.asarray(l)) for l in losses])
                    if losses else np.zeros(0))
                if (monitor is not None
                        and len(monitor.epoch_step_iters) == len(loss_vec)):
                    # replay-skipped windows advance the iteration
                    # counter without recording losses — the monitor's
                    # per-step iteration log keeps each point on its
                    # real x position
                    loss_its = [i + 1 for i in monitor.epoch_step_iters]
                else:
                    start_it = loop_state.iteration - len(loss_vec)
                    loss_its = [start_it + j + 1
                                for j in range(len(loss_vec))]
                for j, lv in enumerate(loss_vec):
                    tb.add_scalar("Loss", float(lv), loss_its[j])
                tb.add_scalar("Throughput", record["throughput"],
                              loop_state.iteration)
                lr = getattr(model, "_lr", None)
                if callable(lr):
                    tb.add_scalar("LearningRate",
                                  float(lr(loop_state.iteration)),
                                  loop_state.iteration)
                elif isinstance(lr, (int, float)):
                    tb.add_scalar("LearningRate", float(lr),
                                  loop_state.iteration)
                if completed:
                    # a mid-epoch end_trigger stop retrains this epoch on
                    # the next fit(); logging its partial params here
                    # would put two histograms under one epoch number
                    _write_param_histograms(tb, model.params, (epoch,),
                                            loop_state.iteration,
                                            n_steps=len(loss_vec))
                tb.writer.flush()
            vtb = getattr(model, "_val_summary", None)
            if vtb is not None and val is not None:
                for k, v in val.items():
                    vtb.add_scalar(k, float(v), loop_state.iteration)
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
            if stop or (end_trigger is not None and end_trigger(loop_state)):
                break

        return history

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
    ``Summary.scala`` histogram path); under fused-epoch dispatch they
    land on the final epoch of each fused block, where the params are
    host-visible."""
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
