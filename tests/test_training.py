"""Training-engine tests — convergence on the 8-device CPU mesh, exercising
the real sharded train step (counterpart of ``keras/models/TrainingSpec.scala``
and ``DistriEstimatorSpec.scala``)."""

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.pipeline.api.keras import Sequential, Model, Input
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense, Embedding, Flatten, merge


def _xor_data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-1, 1, (n, 2)).astype(np.float32)
    y = ((x[:, 0] * x[:, 1]) > 0).astype(np.float32)[:, None]
    return x, y


def test_fit_converges_xor():
    init_zoo_context()
    x, y = _xor_data()
    m = Sequential([
        Dense(32, activation="relu", input_shape=(2,)),
        Dense(32, activation="relu"),
        Dense(1, activation="sigmoid"),
    ])
    m.compile(optimizer="adam", loss="binary_crossentropy", metrics=["accuracy"],
              lr=0.01)
    history = m.fit(x, y, batch_size=64, nb_epoch=30)
    assert history["loss"][-1] < history["loss"][0]
    res = m.evaluate(x, y, batch_size=64)
    assert res["accuracy"] > 0.9


def test_fit_sparse_categorical():
    init_zoo_context()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(256, 10)).astype(np.float32)
    w = rng.normal(size=(10, 3)).astype(np.float32)
    labels = np.argmax(x @ w, axis=1).astype(np.int32)
    m = Sequential([Dense(32, activation="relu", input_shape=(10,)),
                    Dense(3, activation="softmax")])
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              metrics=["accuracy"], lr=0.01)
    m.fit(x, labels, batch_size=64, nb_epoch=20)
    assert m.evaluate(x, labels)["accuracy"] > 0.9


def test_multi_input_fit_and_predict():
    init_zoo_context()
    rng = np.random.default_rng(2)
    xa = rng.normal(size=(128, 4)).astype(np.float32)
    xb = rng.normal(size=(128, 4)).astype(np.float32)
    y = (np.sum(xa, axis=1) > np.sum(xb, axis=1)).astype(np.float32)[:, None]
    a, b = Input(shape=(4,)), Input(shape=(4,))
    out = Dense(1, activation="sigmoid")(merge([Dense(8)(a), Dense(8)(b)], "concat"))
    m = Model(input=[a, b], output=out)
    m.compile(optimizer="adam", loss="binary_crossentropy", lr=0.05)
    m.fit([xa, xb], y, batch_size=32, nb_epoch=15)
    preds = m.predict([xa, xb], batch_size=32)
    assert preds.shape == (128, 1)
    acc = np.mean((preds > 0.5) == (y > 0.5))
    assert acc > 0.85


def test_predict_handles_ragged_tail():
    init_zoo_context()
    m = Sequential([Dense(3, input_shape=(5,))])
    m.init_weights(input_shape=(5,))
    x = np.ones((37, 5), np.float32)  # 37 not divisible by 8 devices
    preds = m.predict(x, batch_size=16)
    assert preds.shape == (37, 3)


def test_resume_fit_continues_epochs():
    init_zoo_context()
    x, y = _xor_data(128)
    m = Sequential([Dense(8, activation="relu", input_shape=(2,)),
                    Dense(1, activation="sigmoid")])
    m.compile(optimizer="adam", loss="bce")
    m.fit(x, y, batch_size=32, nb_epoch=2)
    assert m.finished_epochs == 2
    m.fit(x, y, batch_size=32, nb_epoch=2)
    assert m.finished_epochs == 4


def test_gradient_clipping_runs():
    init_zoo_context()
    x, y = _xor_data(64)
    m = Sequential([Dense(8, activation="relu", input_shape=(2,)),
                    Dense(1, activation="sigmoid")])
    m.compile(optimizer="sgd", loss="bce", clip_norm=1.0, clip_value=0.5, lr=0.1)
    h = m.fit(x, y, batch_size=32, nb_epoch=2)
    assert np.isfinite(h["loss"][-1])


def _mlp(**compile_kwargs):
    m = Sequential([Dense(16, activation="relu", input_shape=(2,)),
                    Dense(1, activation="sigmoid")])
    m.compile(optimizer="adam", loss="binary_crossentropy", lr=0.01,
              **compile_kwargs)
    return m


def _step_counts(opt_state):
    """Every ``count`` leaf of an optax state tree (Adam keeps one)."""
    return [int(leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(opt_state)[0]
            if getattr(path[-1], "name", None) == "count"]


def test_second_fit_reuses_matching_optimizer_state():
    """A second fit goes on from the stored optimizer state where its tree
    matches the optimizer's: Adam's step count continues, it does not
    restart."""
    init_zoo_context()
    x, y = _xor_data(n=64 * 5)
    m = _mlp()
    m.fit(x, y, batch_size=64, nb_epoch=1)
    assert _step_counts(m.opt_state) == [5]
    m.fit(x, y, batch_size=64, nb_epoch=2)
    assert _step_counts(m.opt_state) == [15]
    assert m.finished_iterations == 15


def test_changed_optimizer_structure_resets_state_with_warning(caplog):
    """A stored optimizer state whose tree no longer matches the optimizer
    (clipping added between fits) is dropped with a warning, and the new
    state starts from step 0."""
    import logging
    init_zoo_context()
    x, y = _xor_data(n=64 * 5)
    m = _mlp()
    m.fit(x, y, batch_size=64, nb_epoch=1)
    stale = jax.tree_util.tree_structure(m.opt_state)
    m.compile(optimizer="adam", loss="binary_crossentropy", lr=0.01,
              clip_norm=1.0)
    with caplog.at_level(logging.WARNING,
                         logger="analytics_zoo_tpu.training"):
        h = m.fit(x, y, batch_size=64, nb_epoch=1)
    assert any("optimizer structure changed" in r.getMessage()
               for r in caplog.records)
    assert jax.tree_util.tree_structure(m.opt_state) != stale
    assert _step_counts(m.opt_state) == [5]      # not 10: it was reset
    assert np.isfinite(h["loss"][-1])


def test_fit_compiles_one_training_program(tmp_path):
    """Checkpoints, validation and an end trigger all ride the one step:
    a fit that uses the three compiles ``train.step`` once and no other
    training program, and the loop has no other builder to reach for."""
    from analytics_zoo_tpu.common.triggers import MaxIteration
    from analytics_zoo_tpu.observability import default_registry
    from analytics_zoo_tpu.pipeline.api.keras.training import TrainingLoop

    init_zoo_context()
    x, y = _xor_data(n=64 * 5)
    m = _mlp()
    m.set_checkpoint(str(tmp_path / "ckpt"))
    h = m.fit(x, y, batch_size=64, nb_epoch=3, validation_data=(x, y),
              end_trigger=MaxIteration(12))
    assert m.finished_iterations == 12 and len(h["val_loss"]) == 3
    compiled = {key.split('fn="')[1].rstrip('"}'): v["count"]
                for key, v in default_registry().snapshot().items()
                if key.startswith("zoo_jit_compile_seconds{")}
    train_fns = {fn: n for fn, n in compiled.items()
                 if fn.startswith("train.")}
    assert train_fns == {"train.step": 1, "train.eval_step": 1}, compiled
    assert "train.step" in m.last_fit_report["compile"]
    for gone in ("build_scan_step", "build_epoch_fn", "build_multi_epoch_fn",
                 "_make_scan_body", "_make_epoch_body"):
        assert not hasattr(TrainingLoop, gone), gone
    assert not any(hasattr(m._loop, a)
                   for a in ("_scan_step", "_epoch_fns", "_data_cache"))


def test_iteration_end_trigger_stops_mid_epoch_and_snapshots(tmp_path):
    """``SeveralIteration(7)`` as the END trigger at 5 steps an epoch: the
    fit stops on iteration 7, inside epoch 2, which does not count as
    finished; the snapshot cut at the stop says so, so that a resume
    trains that epoch again."""
    from analytics_zoo_tpu.common.triggers import SeveralIteration
    from analytics_zoo_tpu.utils.checkpoint import CheckpointManager

    init_zoo_context()
    x, y = _xor_data(n=64 * 5)
    m = _mlp()
    m.set_checkpoint(str(tmp_path / "ckpt"), keep=0)
    records = []
    h = m.fit(x, y, batch_size=64, nb_epoch=4, callbacks=[records.append],
              end_trigger=SeveralIteration(7))
    assert m.finished_iterations == 7
    assert m.finished_epochs == 1
    assert len(h["loss"]) == 2
    assert [r["iteration"] for r in records] == [5, 7]
    assert records[-1]["loop_state"].epoch_finished is False
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    assert mgr.steps() == [5, 7]
    metas = {s: mgr._read_manifest(s)["meta"] for s in mgr.steps()}
    assert metas[5]["epoch_finished"] is True and metas[5]["epoch"] == 1
    assert metas[7]["epoch_finished"] is False and metas[7]["epoch"] == 2
