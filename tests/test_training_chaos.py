"""Self-healing training chaos: seeded ``train.grads`` fault plans
against the anomaly sentinels (``common/anomaly.py`` +
``pipeline/api/keras/training.py``), reconciled EXACTLY.

The contract under test (docs/guides/TRAINING.md "Anomaly detection &
recovery"):

* **exact detection** — every injected nan_loss / nan_grad / spike plan
  entry shows up in ``zoo_train_anomaly_total{kind=}`` exactly once,
  classified by kind, with a ``train.anomaly`` event,
* **skip-batch containment** — in ``recover`` mode the anomalous step's
  update is discarded ON DEVICE: final losses and params are
  bit-identical to a control run trained without the poison batches,
  on both the single-step and the scan-chunk dispatch paths,
* **rollback escalation** — past ``zoo.train.max_skips_per_epoch`` the
  loop reloads the last good checkpoint and replays with the offending
  window skipped; repeated rollbacks exhaust the per-fit RetryBudget
  and fail loudly via ``TrainingDiverged`` (never a silent infinite
  loop),
* **off is free** — ``zoo.train.sentinel=off`` builds the historical
  step (no sentinel ops); ``warn`` observes without altering updates,
* **grad clipping** — ``zoo.train.grad_clip`` rescales by global norm
  in the step builders and counts engagements.
"""

import math

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common.context import init_zoo_context
from analytics_zoo_tpu.common.faults import FaultPlan
from analytics_zoo_tpu.observability import (JsonEventSink, default_registry,
                                             read_events)
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.training import TrainingDiverged

import jax

BATCH = 32


def _data(n=256, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, 1)).astype(np.float32)
    return x, (x @ w).astype(np.float32)


def _without_batches(x, y, batch_indices):
    """The poison-free control dataset: the flagged batches' rows removed
    (shuffle is off everywhere here, so batch i is rows
    ``[i*BATCH, (i+1)*BATCH)``)."""
    keep = np.ones(len(x), bool)
    for b in batch_indices:
        keep[b * BATCH:(b + 1) * BATCH] = False
    return x[keep], y[keep]


def _model(lr=0.05):
    m = Sequential([Dense(8, activation="relu", input_shape=(8,)),
                    Dense(1)])
    m.compile(optimizer="adam", loss="mse", lr=lr)
    return m


def _counters(*names):
    """Default-registry values (labeled families use the
    ``name{k="v"}`` snapshot key), absent -> 0 — tests diff
    before/after so they reconcile exactly."""
    snap = default_registry().snapshot()
    out = {}
    for n in names:
        e = snap.get(n, {})
        out[n] = e.get("value", e.get("count", 0))
    return out

ANOM = ('zoo_train_anomaly_total{kind="nan_loss"}',
        'zoo_train_anomaly_total{kind="nan_grad"}',
        'zoo_train_anomaly_total{kind="spike"}',
        "zoo_train_skipped_steps_total", "zoo_train_rollback_total")


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# detection: counters/events reconcile exactly against the plan
# ---------------------------------------------------------------------------

def test_recover_counts_each_kind_exactly_and_contains_them(tmp_path):
    """One nan_loss, one nan_grad, one spike injected: each kind's
    counter goes up exactly once (classification is mutually exclusive),
    all three updates are discarded, and training ends finite — the
    NaN-grad step cannot poison the params because it never applied."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover")
    x, y = _data()
    before = _counters(*ANOM)
    m = _model()
    events = str(tmp_path / "events.jsonl")
    sink = JsonEventSink(events)
    default_registry().add_event_sink(sink)
    # spike at call 7: steps 0,2,4,5,6 applied before it → the EWMA is
    # past its 5-step warmup and a 1e6x norm stands out
    plan = (FaultPlan(seed=3)
            .add("train.grads", "nan_loss", at=(1,))
            .add("train.grads", "nan_grad", at=(3,))
            .add("train.grads", "spike", at=(7,), scale=1e6))
    try:
        with faults.activate(plan):
            h = m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    finally:
        default_registry().remove_event_sink(sink)
        sink.close()
    assert [(s, k) for s, k, _ in plan.fired] == [
        ("train.grads", "nan_loss"), ("train.grads", "nan_grad"),
        ("train.grads", "spike")]
    after = _counters(*ANOM)
    for key, kind in zip(ANOM[:3], ("nan_loss", "nan_grad", "spike")):
        assert after[key] - before[key] == 1, (key, after, before)
    assert after["zoo_train_skipped_steps_total"] \
        - before["zoo_train_skipped_steps_total"] == 3
    assert after["zoo_train_rollback_total"] \
        - before["zoo_train_rollback_total"] == 0
    # skipped losses are excluded from the epoch mean — it stays finite
    assert math.isfinite(h["loss"][0])
    for leaf in jax.tree_util.tree_leaves(m.params):
        assert np.all(np.isfinite(np.asarray(leaf)))
    # one train.anomaly event per injected fault, naming the kind
    evs = [e for e in read_events(events) if e["kind"] == "train.anomaly"]
    assert [e["kinds"] for e in evs] == ["nan_loss", "nan_grad", "spike"]
    assert all(e["action"] == "skip" for e in evs)
    assert [e["iteration"] for e in evs] == [1, 3, 7]


def test_warn_mode_detects_but_applies_updates():
    """``warn``: the anomaly is counted and logged, the update still
    applies — a NaN loss (with clean grads) surfaces as a NaN epoch
    mean, and nothing is skipped."""
    init_zoo_context(faults_enabled=True, train_sentinel="warn")
    x, y = _data()
    before = _counters(*ANOM)
    m = _model()
    plan = FaultPlan(seed=5).add("train.grads", "nan_loss", at=(2,))
    with faults.activate(plan):
        h = m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after = _counters(*ANOM)
    assert [(s, k) for s, k, _ in plan.fired] == [("train.grads",
                                                   "nan_loss")]
    assert after['zoo_train_anomaly_total{kind="nan_loss"}'] \
        - before['zoo_train_anomaly_total{kind="nan_loss"}'] == 1
    assert after["zoo_train_skipped_steps_total"] \
        - before["zoo_train_skipped_steps_total"] == 0
    # warn does not mask: the NaN loss lands in the epoch mean (visible)
    assert math.isnan(h["loss"][0])
    # ...but the params stayed finite (the injected NaN hit only the loss
    # value; the gradients were clean and applied)
    for leaf in jax.tree_util.tree_leaves(m.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


# ---------------------------------------------------------------------------
# skip-mode bit-identity vs a poison-free control
# ---------------------------------------------------------------------------

def test_skip_mode_matches_control_bit_for_bit():
    """The acceptance scenario: a recovered run's final losses AND
    params are bit-identical to a control run trained without the
    poison batches.
    (Both runs compile the identical guarded step; the rng schedule is
    consumed by a dropout-free model, so skipping a batch leaves the
    surviving steps' math untouched.)"""
    init_zoo_context(faults_enabled=True, train_sentinel="recover")
    x, y = _data()
    poisoned = (2, 6)

    m_t = _model()
    plan = (FaultPlan(seed=7)
            .add("train.grads", "nan_loss", at=(2,))
            .add("train.grads", "spike", at=(6,), scale=1e5))
    with faults.activate(plan):
        h_t = m_t.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    assert len(plan.fired) == 2

    xc, yc = _without_batches(x, y, poisoned)
    m_c = _model()
    h_c = m_c.fit(xc, yc, batch_size=BATCH, nb_epoch=1, shuffle=False)

    assert h_t["loss"] == h_c["loss"]          # bit-identical epoch mean
    _leaves_equal(m_t.params, m_c.params)
    _leaves_equal(m_t.opt_state, m_c.opt_state)


def test_sentinel_off_and_warn_match_numerically():
    """``off`` builds the historical step (no sentinel ops at all);
    ``warn`` adds observation only — the trained trajectories agree."""
    x, y = _data()
    init_zoo_context(train_sentinel="off")
    m_off = _model()
    assert m_off._loop._sentinel_config().active is False
    h_off = m_off.fit(x, y, batch_size=BATCH, nb_epoch=2, shuffle=False)

    init_zoo_context(train_sentinel="warn")
    m_warn = _model()
    assert m_warn._loop._sentinel_config().sentinel is True
    h_warn = m_warn.fit(x, y, batch_size=BATCH, nb_epoch=2, shuffle=False)

    np.testing.assert_allclose(h_off["loss"], h_warn["loss"], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(m_off.params),
                    jax.tree_util.tree_leaves(m_warn.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# rollback escalation and the TrainingDiverged budget
# ---------------------------------------------------------------------------

def test_rollback_restores_last_good_and_skips_window_on_replay(tmp_path):
    """Past max_skips_per_epoch the loop reloads the last good snapshot
    and replays the epoch with the flagged window skipped — the
    recovered run equals a control trained without those batches."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_max_skips_per_epoch=2)
    x, y = _data()

    # control: clean epoch 1, then epoch 2 without batches 2,3,4
    m_c = _model()
    m_c.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    xc, yc = _without_batches(x, y, (2, 3, 4))
    h_c = m_c.fit(xc, yc, batch_size=BATCH, nb_epoch=1, shuffle=False)

    m = _model()
    m.set_checkpoint(str(tmp_path / "ckpt"))
    m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)  # ckpt-8
    before = _counters(*ANOM)
    # epoch 2's dispatches are site calls 0..7 → batches 2,3,4 poisoned:
    # 3 skips > budget 2 ⇒ rollback to ckpt-8, replay skips iters 10-12
    plan = FaultPlan(seed=11).add("train.grads", "nan_loss", at=(2, 3, 4))
    with faults.activate(plan):
        h = m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after = _counters(*ANOM)

    assert len(plan.fired) == 3
    assert after["zoo_train_rollback_total"] \
        - before["zoo_train_rollback_total"] == 1
    assert after['zoo_train_anomaly_total{kind="nan_loss"}'] \
        - before['zoo_train_anomaly_total{kind="nan_loss"}'] == 3
    # 3 device-skips in the first attempt + 3 replay-skips after rollback
    assert after["zoo_train_skipped_steps_total"] \
        - before["zoo_train_skipped_steps_total"] == 6
    assert m.finished_epochs == 2
    # the replayed epoch equals the poison-free control bit for bit
    assert h["loss"] == h_c["loss"]
    _leaves_equal(m.params, m_c.params)


def test_rollback_regresses_past_in_memory_progress(tmp_path):
    """Review regression: with a checkpoint trigger coarser than the
    divergence point, the last good snapshot is OLDER than the model's
    published progress. The rollback must actually regress to it (the
    never-regress resume guard is rollback-exempt — counting a rollback
    while silently keeping the diverging state would lie to the
    operator), and the replay's skip set — keyed by (epoch, ordinal),
    not global iteration — must land on the same data windows after the
    regression: the recovered run equals the poison-free control bit
    for bit."""
    from analytics_zoo_tpu.common.triggers import Trigger

    class _Never(Trigger):
        def __call__(self, state):
            return False

    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_max_skips_per_epoch=2)
    x, y = _data()

    # control: epochs 1-2 clean, epoch 3 without batches 2,3,4
    m_c = _model()
    h_c12 = m_c.fit(x, y, batch_size=BATCH, nb_epoch=2, shuffle=False)
    xc, yc = _without_batches(x, y, (2, 3, 4))
    h_c3 = m_c.fit(xc, yc, batch_size=BATCH, nb_epoch=1, shuffle=False)

    m = _model()
    m.set_checkpoint(str(tmp_path / "ckpt"))            # EveryEpoch
    m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)  # ckpt-8
    # second fit cuts NO further snapshots: epoch 2 completes (published
    # progress = iteration 16) while the newest snapshot stays at 8
    m.set_checkpoint(str(tmp_path / "ckpt"), trigger=_Never())
    before = _counters(*ANOM)
    # epoch 2 = site calls 0-7 (clean); epoch 3 = calls 8-15, with its
    # batches 2,3,4 poisoned -> 3 skips > budget 2 -> rollback to ckpt-8
    plan = FaultPlan(seed=23).add("train.grads", "nan_loss",
                                  at=(10, 11, 12))
    with faults.activate(plan):
        h = m.fit(x, y, batch_size=BATCH, nb_epoch=2, shuffle=False)
    after = _counters(*ANOM)

    assert len(plan.fired) == 3
    assert after["zoo_train_rollback_total"] \
        - before["zoo_train_rollback_total"] == 1
    # the replay retrained BOTH epochs (progress regressed to ckpt-8's
    # epoch 1, not silently kept at the diverging epoch 2 state)
    assert m.finished_epochs == 3 and len(h["loss"]) == 2
    assert h["loss"][0] == h_c12["loss"][1]     # epoch 2, bit-identical
    assert h["loss"][1] == h_c3["loss"][0]      # epoch 3 minus poison
    _leaves_equal(m.params, m_c.params)


def test_rollback_budget_exhaustion_raises_training_diverged(tmp_path):
    """A divergence rollback cannot outrun (every step anomalous) must
    exhaust zoo.train.max_rollbacks and raise TrainingDiverged — never
    loop forever, never exit 'successfully' on garbage."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_max_skips_per_epoch=1, train_max_rollbacks=2)
    x, y = _data()
    m = _model()
    m.set_checkpoint(str(tmp_path / "ckpt"))
    m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    before = _counters("zoo_train_rollback_total",
                       'zoo_retry_budget_exhausted_total'
                       '{budget="train.rollback"}')
    plan = FaultPlan(seed=13).add("train.grads", "nan_grad",
                                  at=tuple(range(64)))
    with faults.activate(plan):
        with pytest.raises(TrainingDiverged, match="rollback budget"):
            m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after = _counters("zoo_train_rollback_total",
                      'zoo_retry_budget_exhausted_total'
                      '{budget="train.rollback"}')
    assert after["zoo_train_rollback_total"] \
        - before["zoo_train_rollback_total"] == 2
    assert after['zoo_retry_budget_exhausted_total'
                 '{budget="train.rollback"}'] \
        - before['zoo_retry_budget_exhausted_total'
                 '{budget="train.rollback"}'] == 1
    # the model still holds finite (restored) weights
    for leaf in jax.tree_util.tree_leaves(m.params):
        assert np.all(np.isfinite(np.asarray(leaf)))


def test_escalation_without_checkpoint_raises_training_diverged():
    """Escalation with nothing to roll back to must fail loudly, not
    loop: no set_checkpoint ⇒ TrainingDiverged at the skip budget."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_max_skips_per_epoch=1)
    x, y = _data()
    m = _model()
    plan = FaultPlan(seed=17).add("train.grads", "nan_loss",
                                  at=tuple(range(64)))
    with faults.activate(plan):
        with pytest.raises(TrainingDiverged, match="no checkpoint"):
            m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)


# ---------------------------------------------------------------------------
# zoo.train.grad_clip (satellite)
# ---------------------------------------------------------------------------

def test_grad_clip_engages_and_counts():
    """A tiny clip norm engages on every step (counted exactly); a huge
    one never engages and leaves the trajectory unchanged."""
    x, y = _data()
    init_zoo_context(train_grad_clip=1e-4)
    before = _counters("zoo_train_grad_clip_engaged_total")
    m = _model()
    assert m._loop._sentinel_config().grad_clip == 1e-4
    m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after = _counters("zoo_train_grad_clip_engaged_total")
    assert after["zoo_train_grad_clip_engaged_total"] \
        - before["zoo_train_grad_clip_engaged_total"] == 8

    init_zoo_context(train_grad_clip=1e9)
    m_hi = _model()
    h_hi = m_hi.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after2 = _counters("zoo_train_grad_clip_engaged_total")
    assert after2["zoo_train_grad_clip_engaged_total"] \
        == after["zoo_train_grad_clip_engaged_total"]

    init_zoo_context(train_grad_clip=0.0)
    m_off = _model()
    h_off = m_off.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    np.testing.assert_allclose(h_hi["loss"], h_off["loss"], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(m_hi.params),
                    jax.tree_util.tree_leaves(m_off.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_grad_clip_interplay_with_spike_sentinel():
    """Clipping bounds the applied update; the spike sentinel watches the
    PRE-clip norm — an injected spike is still detected (and skipped)
    even with clipping active, and the clip counter does not count the
    skipped step's engagement as healthy progress."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_grad_clip=1e9)
    x, y = _data()
    m = _model()
    before = _counters(*ANOM)
    plan = FaultPlan(seed=19).add("train.grads", "spike", at=(7,),
                                  scale=1e6)
    with faults.activate(plan):
        m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
    after = _counters(*ANOM)
    assert len(plan.fired) == 1
    assert after['zoo_train_anomaly_total{kind="spike"}'] \
        - before['zoo_train_anomaly_total{kind="spike"}'] == 1
    assert after["zoo_train_skipped_steps_total"] \
        - before["zoo_train_skipped_steps_total"] == 1


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_bad_sentinel_mode_rejected():
    init_zoo_context(train_sentinel="aggressive")
    m = _model()
    x, y = _data(n=64)
    with pytest.raises(ValueError, match="zoo.train.sentinel"):
        m.fit(x, y, batch_size=BATCH, nb_epoch=1)


def test_sentinel_knobs_not_validated_when_off():
    """A (mis-)configured value for the DISABLED sentinel must not abort
    training that never reads it — validation is scoped to mode != off
    (zoo.train.grad_clip stands alone and stays validated)."""
    init_zoo_context(conf={"zoo.train.spike_factor": 0.5,
                           "zoo.train.max_rollbacks": 0})
    m = _model()
    x, y = _data(n=64)
    m.fit(x, y, batch_size=BATCH, nb_epoch=1)          # sentinel off: fine
    init_zoo_context(conf={"zoo.train.spike_factor": 0.5,
                           "zoo.train.sentinel": "warn"})
    m2 = _model()
    with pytest.raises(ValueError, match="spike_factor"):
        m2.fit(x, y, batch_size=BATCH, nb_epoch=1)
    # a negative skip budget would escalate a HEALTHY recover run at the
    # first drain (0 > -1) — rejected up front like the other knobs
    init_zoo_context(conf={"zoo.train.max_skips_per_epoch": -1,
                           "zoo.train.sentinel": "recover"})
    m3 = _model()
    with pytest.raises(ValueError, match="max_skips_per_epoch"):
        m3.fit(x, y, batch_size=BATCH, nb_epoch=1)


def test_spike_check_waits_for_a_nonzero_baseline():
    """A (near-)zero warm-up baseline — fully-masked window, frozen
    phase, dead-ReLU start — makes the relative spike test meaningless:
    without the EWMA_FLOOR gate the first real gradient would flag,
    recover mode would skip it, params and baseline would never move,
    and a HEALTHY run would livelock into rollback escalation."""
    from analytics_zoo_tpu.common import anomaly
    import jax.numpy as jnp

    state = anomaly.init_state()
    zero = jnp.zeros((), jnp.float32)
    for _ in range(anomaly.WARMUP_STEPS + 2):     # warm up on zero grads
        flags, state = anomaly.check(zero, zero, state, 10.0)
        assert int(flags) == 0
    # first real gradient after the dead phase: NOT a spike
    flags, state = anomaly.check(jnp.asarray(0.3, jnp.float32),
                                 jnp.asarray(1.0, jnp.float32), state, 10.0)
    assert int(flags) == 0
    # but once the baseline is real, a genuine 100x spike still flags
    for _ in range(3):
        flags, state = anomaly.check(jnp.asarray(0.3, jnp.float32),
                                     jnp.asarray(1.0, jnp.float32),
                                     state, 10.0)
        assert int(flags) == 0
    flags, _ = anomaly.check(jnp.asarray(0.3, jnp.float32),
                             jnp.asarray(100.0, jnp.float32), state, 10.0)
    assert int(flags) == anomaly.SPIKE
