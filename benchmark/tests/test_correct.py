"""``correct`` has to come out false for what it exists to catch.

* The control: the configuration's reference, put in the program's place and
  computed with fp8 products (the nearest precision below the bf16 the
  configurations state), is not correct.
* The faults a training cell can have, planted under a whole run of the
  harness (``kinds/train.py::run`` without its look for a chip): a step that
  leaves the parameters unchanged; half of the batch left out, the mean taken
  over the rest; the chips' exchange left out, so that a step sees one
  chip's rows only.

The limits here are those of the tiny cells (``tiny.py``) on the CPU, set
from readings of four seeds of each side as ``benchmark/limits/*.json`` are
set from the chip's (program at most / control at least: gpt
grad_error_median_leaf 4.1e-3 / 2.6e-2, bert 1.2e-2 / 3.4e-2).
"""

import time

import numpy as np
import pytest

from benchmark.kinds import train
from benchmark.lib import compare, reference_run
from benchmark.tests import tiny

LIMITS = {
    "gpt": {"loss_step1": 5e-5, "loss_step2": 5e-5, "loss_step3": 5e-5,
            "grad_norm_worst_leaf": 2e-2, "grad_error_worst_leaf": 0.3,
            "grad_error_median_leaf": 1.2e-2, "change_norm_worst_leaf": 0.1},
    "bert": {"loss_step1": 6e-3, "loss_step2": 6e-3, "loss_step3": 6e-3,
             "grad_norm_worst_leaf": 0.1, "grad_error_worst_leaf": 0.3,
             "grad_error_median_leaf": 1.6e-2, "change_norm_worst_leaf": 0.5},
}
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(name, seed):
    cfg, traffic = tiny.CELLS[name]()
    return train.run({"name": "tiny_" + name, "chips": traffic["chips"]},
                     cfg, traffic, LIMITS[name], {}, seed=seed, seconds=0.5,
                     trace=False, t_process=time.perf_counter(),
                     require_chip=False)


@pytest.mark.parametrize("name", ["gpt", "bert"])
def test_sound_run_is_correct(name):
    result = run_cell(name, seed=2 ** 31 + 11)
    assert result["correct"] is True, result["compared"]
    assert list(result)[:5] == CONTRACT_KEYS
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("name", ["gpt", "bert"])
def test_control_in_fp8_is_not_correct(name):
    cfg, traffic = tiny.CELLS[name]()
    model_lib = reference_run.load("models", cfg["model"])
    ref = reference_run.load("reference", cfg["reference"])
    for seed in (11, 12, 13):
        rng = np.random.default_rng(seed)
        batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
                   for _ in range(train.VERIFY_STEPS)]
        want = reference_run.three_steps(ref, cfg, seed, batches, 1)
        control = reference_run.three_steps(ref, cfg, seed, batches, 1,
                                            mode="fp8")
        ok, rows = compare.compare(control, want, LIMITS[name])
        assert not ok, rows


def _keep_rows(monkeypatch, share):
    """Break the timed path: the loss sees the first ``share`` of a step's
    rows only, and takes its mean over them."""
    import jax

    from analytics_zoo_tpu.pipeline.api.keras.training import TrainingLoop
    sound = TrainingLoop._loss_application

    def broken(self):
        apply_loss = sound(self)

        def fewer(p, net_state, x, y, rng):
            def cut(a):
                return a[:int(a.shape[0] * share)]
            return apply_loss(p, net_state, jax.tree.map(cut, x), cut(y), rng)
        return fewer
    monkeypatch.setattr(TrainingLoop, "_loss_application", broken)


def _state_unchanged(monkeypatch, share):
    import optax
    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)


@pytest.mark.parametrize("plant,share", [
    (_state_unchanged, None), (_keep_rows, 0.5), (_keep_rows, 0.25)],
    ids=["state_unchanged", "half_batch_left_out", "exchange_left_out"])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, plant, share):
    plant(monkeypatch, share)
    result = run_cell("gpt", seed=12)
    assert result["correct"] is False, result["compared"]
