"""Observability: TensorBoard event writer/reader + set_tensorboard wiring.

Golden-tested in BOTH directions against independent implementations:
* our writer's files parse with tensorboard's own EventAccumulator,
* torch.utils.tensorboard's files parse with our reader.
"""

import numpy as np
import pytest

from analytics_zoo_tpu.common.context import init_zoo_context
from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.utils.tensorboard import (EventFileWriter,
                                                 TrainSummary, read_scalars)


def test_writer_roundtrip_own_reader(tmp_path):
    w = EventFileWriter(str(tmp_path))
    w.add_scalar("Loss", 1.5, 1, wall_time=100.0)
    w.add_scalar("Loss", 0.75, 2, wall_time=101.0)
    w.add_scalar("Throughput", 1e4, 2, wall_time=101.5)
    w.close()
    pts = read_scalars(str(tmp_path), "Loss")
    assert [(s, round(v, 4)) for s, v, _, _ in pts] == [(1, 1.5), (2, 0.75)]
    thr = read_scalars(str(tmp_path), "Throughput")
    assert len(thr) == 1 and abs(thr[0][1] - 1e4) < 1


def test_writer_files_readable_by_tensorboard(tmp_path):
    """Files must load in the real TensorBoard backend (format oracle)."""
    ea_mod = pytest.importorskip(
        "tensorboard.backend.event_processing.event_accumulator")
    w = EventFileWriter(str(tmp_path))
    for i, v in enumerate([3.0, 2.0, 1.0]):
        w.add_scalar("Loss", v, i + 1, wall_time=50.0 + i)
    w.close()
    acc = ea_mod.EventAccumulator(str(tmp_path))
    acc.Reload()
    assert "Loss" in acc.Tags()["scalars"]
    events = acc.Scalars("Loss")
    assert [e.step for e in events] == [1, 2, 3]
    np.testing.assert_allclose([e.value for e in events], [3.0, 2.0, 1.0])


def test_reader_parses_torch_written_files(tmp_path):
    """Our reader on files produced by an independent writer."""
    tb = pytest.importorskip("torch.utils.tensorboard")
    w = tb.SummaryWriter(log_dir=str(tmp_path))
    w.add_scalar("acc", 0.25, 7)
    w.add_scalar("acc", 0.5, 8)
    w.close()
    pts = read_scalars(str(tmp_path), "acc")
    assert [(s, round(v, 4)) for s, v, _, _ in pts] == [(7, 0.25), (8, 0.5)]


def test_corrupt_record_detected(tmp_path):
    w = EventFileWriter(str(tmp_path))
    w.add_scalar("Loss", 1.0, 1)
    w.close()
    with open(w.path, "r+b") as f:
        f.seek(-3, 2)  # flip a byte inside the last record payload/crc
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(IOError):
        read_scalars(str(tmp_path))


def test_resolve_lr_matches_actual_schedule():
    """LearningRate summaries must track the REAL schedule, not the raw
    lr kwarg (decay/defaults included)."""
    from analytics_zoo_tpu.pipeline.api.keras import optimizers as optim_lib
    sched = optim_lib.resolve_lr("sgd", lr=0.1, decay=0.01)
    assert callable(sched)
    np.testing.assert_allclose(sched(10), 0.1 / (1 + 0.01 * 10))
    assert optim_lib.resolve_lr("adam") == 0.001  # signature default
    import optax
    assert optim_lib.resolve_lr(optax.sgd(0.1)) is None


def test_fit_writes_summaries_and_reads_back(tmp_path):
    init_zoo_context()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    yc = (x.sum(axis=1) > 0).astype(np.int32)
    m = Sequential()
    m.add(Dense(16, input_shape=(8,), activation="relu"))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer="adam", loss="scce", metrics=["accuracy"], lr=0.01)
    m.set_tensorboard(str(tmp_path), "app")
    m.fit(x, yc, batch_size=32, nb_epoch=3, validation_data=(x, yc))

    loss = m.get_train_summary("Loss")
    steps_per_epoch = 256 // 32
    assert loss.shape == (3 * steps_per_epoch, 3)
    assert list(loss[:, 0]) == list(range(1, 3 * steps_per_epoch + 1))
    # losses trend down over training
    assert loss[-steps_per_epoch:, 1].mean() < loss[:steps_per_epoch, 1].mean()

    thr = m.get_train_summary("Throughput")
    assert thr.shape[0] == 3 and (thr[:, 1] > 0).all()
    lr = m.get_train_summary("LearningRate")
    assert lr.shape[0] == 3 and np.allclose(lr[:, 1], 0.01)

    vacc = m.get_validation_summary("accuracy")
    assert vacc.shape[0] == 3
    assert (vacc[:, 1] >= 0).all() and (vacc[:, 1] <= 1).all()
    # directory layout matches the reference: <log_dir>/<app>/train|validation
    assert (tmp_path / "app" / "train").is_dir()
    assert (tmp_path / "app" / "validation").is_dir()


def test_set_profile_captures_trace(tmp_path):
    """set_profile(dir) traces the next fit (one-shot) and writes xplane
    files readable by TB's profile plugin."""
    import glob
    import numpy as np
    from analytics_zoo_tpu.common.context import init_zoo_context
    from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    init_zoo_context()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(2, activation="softmax"))
    m.init_weights(sample_input=x)
    m.compile(optimizer="adam", loss="scce")
    m.set_profile(str(tmp_path / "prof"))
    m.fit(x, y, batch_size=16, nb_epoch=1)
    traces = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                       recursive=True)
    assert traces, "no profiler trace written"
    # one-shot: the second fit must not require/overwrite a trace
    assert getattr(m, "_profile_dir", None) is None
    m.fit(x, y, batch_size=16, nb_epoch=1)


def test_histogram_roundtrip_own_reader(tmp_path):
    """add_histogram → read_histograms preserves the HistogramProto stats
    (the reference's Summary.scala histogram path)."""
    from analytics_zoo_tpu.utils.tensorboard import (EventFileWriter,
                                                     read_histograms)
    w = EventFileWriter(str(tmp_path))
    rng = np.random.default_rng(0)
    vals = rng.normal(2.0, 3.0, 1000)
    w.add_histogram("weights/W", vals, step=7)
    w.add_histogram("weights/W", vals * 2, step=8)
    w.close()
    pts = read_histograms(str(tmp_path), "weights/W")
    assert [p[0] for p in pts] == [7, 8]
    st = pts[0][1]
    assert st["num"] == 1000
    np.testing.assert_allclose(st["min"], vals.min())
    np.testing.assert_allclose(st["max"], vals.max())
    np.testing.assert_allclose(st["sum"], vals.sum())
    np.testing.assert_allclose(st["sum_squares"], (vals * vals).sum())
    assert len(st["bucket"]) == len(st["bucket_limit"]) == 30
    assert sum(st["bucket"]) == 1000
    # constant tensor: single-bucket histogram
    w2 = EventFileWriter(str(tmp_path / "c"))
    w2.add_histogram("b", np.full(5, 3.5), step=1)
    w2.close()
    st2 = read_histograms(str(tmp_path / "c"), "b")[0][1]
    assert st2["bucket"] == [5.0] and st2["bucket_limit"] == [3.5]


def test_histograms_readable_by_tensorboard(tmp_path):
    """torch's TB reader (a third-party implementation of the same proto)
    parses our histogram events."""
    tbe = pytest.importorskip("tensorboard.backend.event_processing"
                              ".event_accumulator")
    from analytics_zoo_tpu.utils.tensorboard import EventFileWriter
    w = EventFileWriter(str(tmp_path))
    w.add_histogram("h", np.arange(100, dtype=np.float64), step=3)
    w.close()
    acc = tbe.EventAccumulator(str(tmp_path),
                               size_guidance={tbe.HISTOGRAMS: 0})
    acc.Reload()
    hists = acc.Histograms("h")
    assert len(hists) == 1 and hists[0].step == 3
    assert hists[0].histogram_value.num == 100


def test_fit_writes_parameter_histograms(tmp_path):
    """set_tensorboard(parameters_every_epochs=1) logs per-layer weight
    histograms from fit, one per epoch; ``parameters_every_epochs=2`` only
    at the epochs the frequency divides."""
    from analytics_zoo_tpu.common.context import (init_zoo_context,
                                                  reset_zoo_context)
    from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.utils.tensorboard import read_histograms

    reset_zoo_context()
    init_zoo_context()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,), name="d1"))
    m.add(Dense(2, activation="softmax", name="d2"))
    m.init_weights(sample_input=x)
    m.compile(optimizer="adam", loss="scce")
    m.set_tensorboard(str(tmp_path), "app", parameters_every_epochs=1)
    m.fit(x, y, batch_size=16, nb_epoch=2)
    train_dir = str(tmp_path / "app" / "train")
    pts = read_histograms(train_dir)
    tags = {t for _, _, _, t in pts}
    assert any(t.startswith("Parameters/") and "d1" in t for t in tags), tags
    w_pts = [p for p in pts if "d1" in p[3] and p[3].endswith("W")]
    assert len(w_pts) == 2          # one per epoch
    assert w_pts[0][1]["num"] == 4 * 8

    # every second epoch: of three epochs (4 steps each) only epoch 2
    reset_zoo_context()
    init_zoo_context()
    m2 = Sequential()
    m2.add(Dense(8, activation="relu", input_shape=(4,), name="d1"))
    m2.add(Dense(2, activation="softmax", name="d2"))
    m2.init_weights(sample_input=x)
    m2.compile(optimizer="adam", loss="scce")
    m2.set_tensorboard(str(tmp_path / "every2"), "app",
                       parameters_every_epochs=2)
    m2.fit(x, y, batch_size=16, nb_epoch=3)
    pts2 = read_histograms(str(tmp_path / "every2" / "app" / "train"))
    w2 = [p for p in pts2 if "d1" in p[3] and p[3].endswith("W")]
    assert [p[0] for p in w2] == [8], [p[0] for p in w2]
    reset_zoo_context()


def test_histogram_nonfinite_weights_do_not_crash(tmp_path):
    """A diverged run (NaN/inf weights) must degrade to a degenerate
    histogram, not crash fit() from the logging path."""
    from analytics_zoo_tpu.utils.tensorboard import (EventFileWriter,
                                                     read_histograms)
    w = EventFileWriter(str(tmp_path))
    w.add_histogram("n", np.array([1.0, np.nan, 2.0, np.inf]), step=1)
    w.add_histogram("all_bad", np.array([np.nan, np.inf]), step=1)
    w.close()
    st = read_histograms(str(tmp_path), "n")[0][1]
    assert st["num"] == 2 and st["min"] == 1.0 and st["max"] == 2.0
    st2 = read_histograms(str(tmp_path), "all_bad")[0][1]
    assert st2["num"] == 1 and sum(st2["bucket"]) == 1


def test_set_summary_trigger_accepts_trigger_objects(tmp_path):
    """Reference API parity: ``setSummaryTrigger(name, trigger)`` takes a
    Trigger object (not just the every-N-epochs int shorthand), and the
    reference's always-on scalar families are accepted as no-ops."""
    from analytics_zoo_tpu.common.triggers import EveryEpoch

    ts = TrainSummary(str(tmp_path), "app")
    try:
        assert ts.set_summary_trigger("Parameters", 2) is ts
        assert ts.parameters_every_epochs == 2
        assert ts.parameters_trigger is None

        trig = EveryEpoch()
        ts.set_summary_trigger("Parameters", trig)
        assert ts.parameters_trigger is trig
        assert ts.parameters_every_epochs is None

        # Loss/Throughput/LearningRate are written unconditionally here —
        # their reference triggers must not raise
        assert ts.set_summary_trigger("LearningRate", EveryEpoch()) is ts
        assert ts.set_summary_trigger("Loss", 3) is ts

        # ...but a MALFORMED trigger raises identically for every family:
        # the no-op must not swallow a typo that would blow up later when
        # the same call is made for "Parameters"
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Loss", "weekly")
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Throughput", EveryEpoch)  # class, no ()
        with pytest.raises(ValueError):
            ts.set_summary_trigger("LearningRate", 0)

        # the pre-Trigger keyword spelling keeps working
        assert ts.set_summary_trigger("Parameters", every_epochs=4) is ts
        assert ts.parameters_every_epochs == 4
        assert ts.parameters_trigger is None

        with pytest.raises(ValueError):
            ts.set_summary_trigger("NoSuchFamily", 1)
        with pytest.raises(ValueError):
            ts.set_summary_trigger("Parameters", 0)
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Parameters", "weekly")
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Parameters", 1, every_epochs=2)
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Parameters")
    finally:
        ts.close()


def test_parameter_histograms_honor_trigger_object(tmp_path):
    """The histogram writer evaluates a Trigger-form "Parameters" trigger
    at epoch boundaries (where params are host-visible)."""
    from analytics_zoo_tpu.common.triggers import SeveralIteration
    from analytics_zoo_tpu.pipeline.api.keras.training import (
        _write_param_histograms)
    from analytics_zoo_tpu.utils.tensorboard import read_histograms

    ts = TrainSummary(str(tmp_path), "app")
    params = {"d1": {"W": np.ones((4, 8), np.float32)}}
    ts.set_summary_trigger("Parameters", SeveralIteration(10))
    _write_param_histograms(ts, params, epoch=1, iteration=5)
    _write_param_histograms(ts, params, epoch=2, iteration=10)
    ts.close()
    pts = read_histograms(str(tmp_path / "app" / "train"))
    assert len(pts) == 1            # only the iteration-10 boundary fired
    assert pts[0][3] == "Parameters/d1/W"


def test_trigger_fire_landing_mid_epoch_is_not_dropped(tmp_path):
    """``_fired_within`` window semantics: a SeveralIteration fire landing
    MID-epoch (iteration 7 with 5 steps/epoch) is acted on at that epoch's
    boundary, like the loop's checkpoint/validation triggers — not dropped
    because no boundary iteration is an exact multiple."""
    from analytics_zoo_tpu.common.triggers import SeveralIteration
    from analytics_zoo_tpu.pipeline.api.keras.training import (
        _write_param_histograms)
    from analytics_zoo_tpu.utils.tensorboard import read_histograms

    params = {"d1": {"W": np.ones((4, 8), np.float32)}}
    ts = TrainSummary(str(tmp_path), "app")
    ts.set_summary_trigger("Parameters", SeveralIteration(7))
    # boundaries 5, 10, 15: fires land at 7 (in (5,10]) and 14 (in (10,15])
    _write_param_histograms(ts, params, 1, 5, n_steps=5)
    _write_param_histograms(ts, params, 2, 10, n_steps=5)
    _write_param_histograms(ts, params, 3, 15, n_steps=5)
    ts.close()
    steps = sorted(s for s, _, _, _ in
                   read_histograms(str(tmp_path / "app" / "train")))
    assert steps == [10, 15], steps


def test_set_summary_trigger_numeric_coercion(tmp_path):
    """The pre-Trigger signature coerced with int(...): numpy integers and
    whole floats must keep working."""
    ts = TrainSummary(str(tmp_path), "app")
    try:
        ts.set_summary_trigger("Parameters", np.int64(2))
        assert ts.parameters_every_epochs == 2
        ts.set_summary_trigger("Parameters", 3.0)
        assert ts.parameters_every_epochs == 3
        with pytest.raises(TypeError):
            ts.set_summary_trigger("Parameters", True)
    finally:
        ts.close()
