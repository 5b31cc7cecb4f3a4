"""``fit`` holds ONE copy of weights, layer state and optimizer state while
steps run (ROADMAP S10, first half): the model's trees are handed to the
loop, not cloned beside it, and handed back by reference when the loop
ends, however it ends. Probed on ``tests/test_decoder_stack.py``'s tiny
decoder by counting device BUFFERS (``jax.live_arrays()`` lists a buffer
twice once ``jax.device_get`` has looked at it). Also the tier-1 twin of
``benchmark/tests/test_reference_memory.py``: what the reference side of
``correct`` keeps on the device, and ``tools/size.py``'s counts."""

import gc
import importlib.util
import json
import os
import signal

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.common import init_zoo_context
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.pipeline.api.keras.training import TrainingPreempted

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


stack = _load(os.path.join(ROOT, "tests", "test_decoder_stack.py"),
              "_tiny_decoder_stack")
STEPS = 6


def _live_bytes():
    """Bytes of the buffers alive on the first device, each counted once."""
    first, seen = jax.devices()[0], {}
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            if shard.device == first:
                seen[shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    return sum(seen.values())


def _tree_bytes(tree):
    return sum(a.addressable_shards[0].data.nbytes
               for a in jax.tree.leaves(tree) if isinstance(a, jax.Array))


def _valid(tree):
    return all(not a.is_deleted() for a in jax.tree.leaves(tree)
               if isinstance(a, jax.Array))


def _same(tree, other):
    a, b = jax.tree.leaves(tree), jax.tree.leaves(other)
    return len(a) == len(b) and all(x is y for x, y in zip(a, b))


def _model_and_data(seed=3):
    cfg = stack.tiny_cfg()
    model_lib = stack._bench("models")
    model = model_lib.build(cfg, stack.TRAFFIC)
    rng = np.random.default_rng(seed)
    x, y = model_lib.features(cfg, stack.TRAFFIC, rng,
                              stack.TRAFFIC["batch"] * STEPS)
    return model, FeatureSet.array(x, y, shuffle=False)


class _Probe:
    """Stands in for the loop's compiled step: runs ``at(call number)``
    before the real step and keeps what the last real step returned."""

    def __init__(self, loop, at):
        self.loop, self.real, self.at = loop, loop._train_step, at
        self.calls, self.last = 0, None
        loop._train_step = self

    def __call__(self, *args):
        self.calls += 1
        self.at(self.calls)
        self.last = self.real(*args)
        return self.last

    def remove(self):
        self.loop._train_step = self.real


def _fitted_once():
    """A model after one fit of one epoch: its step is compiled, its state
    lives replicated on the mesh."""
    model, data = _model_and_data()
    model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    return model, data


def test_one_copy_of_the_state_is_alive_while_steps_run():
    init_zoo_context()
    model, data = _fitted_once()
    state = _tree_bytes((model.params, model.net_state, model.opt_state))
    assert model.last_fit_report["state"]["bytes"] >= state > 100_000
    held = [model.params]       # read before the fit: consumed by it
    gc.collect()
    seen = []
    probe = _Probe(model._loop, lambda k: seen.append(_live_bytes()))
    model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=2)
    probe.remove()
    assert probe.calls == 2 * STEPS
    # one copy and what a step's data and losses take, never two: at every
    # step of both epochs, the boundary between them included
    assert max(seen) < 1.25 * state, (max(seen), state)
    assert min(seen) >= state
    report = model.last_fit_report["state"]
    assert report["source"] == "handed_over"
    assert report["published"] == "handed_back"
    assert report["bytes"] == state
    # the contract's price: what was read from the model before is gone
    assert not _valid(held[0])
    # and what the model holds now is what the last step returned
    assert _valid((model.params, model.net_state, model.opt_state))
    assert _same(model.params, probe.last[0])
    assert _same(model.opt_state, probe.last[1])
    assert _same(model.net_state, probe.last[2])
    assert model.finished_epochs == 3
    assert model.finished_iterations == 3 * STEPS


def test_device_get_gives_a_copy_that_outlasts_a_fit():
    init_zoo_context()
    model, data = _fitted_once()
    host = jax.device_get(model.params)
    want = [np.array(a) for a in jax.tree.leaves(host)]
    model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    for a, b in zip(jax.tree.leaves(host), want):
        np.testing.assert_array_equal(a, b)


class _Boom(RuntimeError):
    pass


def test_an_exception_inside_an_epoch_leaves_the_models_trees_valid():
    """The step raises at its third call of an epoch: the model holds the
    loop's live trees (what the second step returned), its progress counts
    as at the last boundary, and a second fit continues from them."""
    init_zoo_context()
    model, data = _fitted_once()

    def at(k):
        if k == 3:
            raise _Boom("step 3")
    probe = _Probe(model._loop, at)
    with pytest.raises(_Boom):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    probe.remove()
    assert _valid((model.params, model.net_state, model.opt_state))
    assert _same(model.params, probe.last[0])
    assert _same(model.opt_state, probe.last[1])
    assert _same(model.net_state, probe.last[2])
    assert model.finished_epochs == 1 and model.finished_iterations == STEPS
    assert model.last_fit_report["state"]["published"] == "handed_back"
    before = jax.device_get(model.params)
    history = model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    assert model.last_fit_report["state"]["source"] == "handed_over"
    assert np.isfinite(history["loss"]).all()
    assert model.finished_epochs == 2
    moved = [float(np.abs(np.asarray(a) - b).max()) for a, b in zip(
        jax.tree.leaves(model.params), jax.tree.leaves(before))]
    assert max(moved) > 0


def test_a_failing_callback_leaves_the_models_trees_valid():
    init_zoo_context()
    model, data = _fitted_once()

    def boom(record):
        assert _valid(record["params"]) and record["params"] is model.params
        raise _Boom("callback")
    with pytest.raises(_Boom):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=2,
                  callbacks=[boom])
    assert _valid((model.params, model.net_state, model.opt_state))
    assert model.finished_epochs == 2


def test_a_preemption_leaves_the_models_trees_valid(tmp_path):
    """SIGTERM during the second step: a final checkpoint at that step's
    boundary, ``TrainingPreempted``, and the model holds what that step
    returned; a fresh fit on the same model goes on from there."""
    init_zoo_context(checkpoint_on_sigterm=True)
    model, data = _fitted_once()
    model.set_checkpoint(str(tmp_path / "ckpt"))

    def at(k):
        if k == 2:
            os.kill(os.getpid(), signal.SIGTERM)
    probe = _Probe(model._loop, at)
    with pytest.raises(TrainingPreempted):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    probe.remove()
    assert probe.calls == 2
    assert _valid((model.params, model.net_state, model.opt_state))
    assert _same(model.params, probe.last[0])
    assert _same(model.opt_state, probe.last[1])
    assert model.finished_iterations == STEPS + 2
    assert model.last_fit_report["state"]["published"] == "handed_back"
    history = model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    assert np.isfinite(history["loss"]).all()
    assert model.finished_epochs == 2


def test_with_a_checkpoint_the_retry_keeps_a_boundary_copy_never_three(
        tmp_path):
    """With a checkpoint directory the model keeps a copy of the last
    epoch boundary while another epoch runs (what a retry falls back to
    when the newest snapshot is torn): two sets then, never three, one in
    the first epoch; a failure inside the later epoch leaves the model at
    that boundary, not at the live trees."""
    init_zoo_context()
    model, data = _fitted_once()
    model.set_checkpoint(str(tmp_path / "ckpt"))
    state = _tree_bytes((model.params, model.net_state, model.opt_state))
    gc.collect()
    seen, boundary = [], []

    def at(k):
        seen.append(_live_bytes())
        if k == STEPS + 1:
            boundary.append(jax.device_get(model.params))
        if k == STEPS + 3:
            raise ValueError("not retried: a user's error")
    probe = _Probe(model._loop, at)
    with pytest.raises(ValueError):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=3)
    probe.remove()
    assert max(seen[:STEPS]) < 1.25 * state
    assert 2 * state <= max(seen[STEPS:]) < 2.25 * state
    assert model.last_fit_report["state"]["published"] == "cloned"
    assert _valid((model.params, model.net_state, model.opt_state))
    assert not _same(model.params, probe.last[0])
    for a, b in zip(jax.tree.leaves(model.params),
                    jax.tree.leaves(boundary[0])):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert model.finished_epochs == 2


def test_state_a_failed_step_consumed_comes_back_from_the_checkpoint(
        tmp_path):
    """A step that took the state and then failed leaves nothing alive:
    with a checkpoint the retry restores it; without one the next fit
    says what happened instead of reading deleted arrays."""
    init_zoo_context()
    model, data = _fitted_once()
    model.set_checkpoint(str(tmp_path / "ckpt"))
    model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)

    class Eats(_Probe):
        def __call__(self, *args):
            self.calls += 1
            if self.calls == 2:
                for a in jax.tree.leaves(args[:3]):
                    a.delete()
                raise _Boom("the device failed under the step")
            return self.real(*args)
    probe = Eats(model._loop, None)
    history = model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    probe.remove()
    assert model.last_fit_report["state"]["source"] == "restored"
    assert _valid((model.params, model.net_state, model.opt_state))
    assert np.isfinite(history["loss"]).all() and model.finished_epochs == 3
    model._checkpoint = None
    for a in jax.tree.leaves(model.params):
        a.delete()
    with pytest.raises(RuntimeError, match="consumed by a training step"):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)


class _Failed:
    """What a step that fails on the device hands out: not deleted, and
    raising as soon as it is waited for or read."""

    def block_until_ready(self):
        raise _Boom("the step failed on the device")

    def is_ready(self):
        return True


def test_usable_tells_deleted_and_failed_arrays_from_live_ones():
    from analytics_zoo_tpu.pipeline.api.keras import training
    live = {"a": jax.numpy.ones(3), "b": None, "c": 2}
    assert training._usable(live)
    assert not training._usable({"a": jax.numpy.ones(3), "b": _Failed()})
    gone = jax.numpy.ones(3)
    gone.delete()
    assert not training._usable({"a": gone})


def test_a_step_that_fails_on_the_device_is_not_published():
    """The epoch's last step takes the state and fails on the device: its
    outputs are arrays that raise when waited for, which the drain does.
    They are not handed to the model as if they were weights: the model
    holds what the step consumed, and without a checkpoint the next fit
    says so (docs: the weights are lost; ``device_get`` before the fit, or
    ``set_checkpoint``, keeps them)."""
    init_zoo_context()
    model, data = _fitted_once()

    class FailsOnDevice(_Probe):
        def __call__(self, *args):
            self.calls += 1
            out = self.real(*args)
            if self.calls < STEPS:
                return out
            return jax.tree.map(lambda a: _Failed(), out)
    probe = FailsOnDevice(model._loop, None)
    with pytest.raises(Exception):      # the drain cannot read the loss
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)
    probe.remove()
    assert not _valid(model.params)
    assert not any(isinstance(a, _Failed) for a in jax.tree.leaves(
        (model.params, model.net_state, model.opt_state)))
    with pytest.raises(RuntimeError, match="consumed by a training step"):
        model.fit(data, batch_size=stack.TRAFFIC["batch"], nb_epoch=1)


@pytest.mark.parametrize("kw", [{}, {"dtype": "bfloat16"},
                                {"quantize": "int8"}],
                         ids=["float32", "bfloat16", "int8"])
def test_an_inference_model_outlasts_a_later_fit_of_its_model(kw):
    """``InferenceModel.from_keras`` keeps arrays of its own: the fit that
    follows consumes the model's, and the served weights stay the ones it
    was made from."""
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    init_zoo_context()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 24)).astype(np.float32)
    y = rng.integers(0, 4, 256).astype(np.int32)
    m = Sequential([Dense(32, activation="relu", input_shape=(24,)),
                    Dense(4, activation="softmax")])
    m.compile(optimizer="adam", loss="scce", lr=0.05)
    m.fit(x, y, batch_size=64, nb_epoch=1)
    im = InferenceModel().from_keras(m, **kw)
    before = np.array(im.predict(x[:64]))
    m.fit(x, y, batch_size=64, nb_epoch=2)
    np.testing.assert_array_equal(np.array(im.predict(x[:64])), before)
    assert np.abs(m.predict(x[:64], batch_size=64) - before).max() > 1e-3


# ---------------------------------------------------------------------------
# the reference side of `correct`, and tools/size.py
# ---------------------------------------------------------------------------

def test_reference_keeps_its_stated_bytes_a_parameter_on_the_tiny_decoder():
    from benchmark.kinds import train
    from benchmark.lib import reference_run
    cfg = stack.tiny_cfg()
    model_lib, ref = stack._bench("models"), stack._bench("reference")
    traffic = dict(stack.TRAFFIC, batch=16)
    rng = np.random.default_rng(5)
    batches = [model_lib.features(cfg, traffic, rng, traffic["batch"])
               for _ in range(train.VERIFY_STEPS)]
    gc.collect()
    before, seen = _live_bytes(), []
    out = reference_run.three_steps(
        ref, cfg, 5, batches, 1,
        probe=lambda label: seen.append((label, _live_bytes() - before)))
    n = out["parameters"]
    most = {label: max(b for at, b in seen if at == label)
            for label in ("block", "step")}
    slack = 16 * 1024 + sum(np.asarray(a)[:1].nbytes
                            for a in jax.tree.leaves(batches[0]))
    assert (reference_run.BYTES_PER_PARAMETER_IN_BLOCKS,
            reference_run.BYTES_PER_PARAMETER_IN_STEP) == (12, 16)
    assert 12 * n <= most["block"] <= 12 * n + slack, most["block"] / n
    assert 16 * n <= most["step"] <= 16 * n + slack, most["step"] / n


@pytest.mark.parametrize("name,parameters", [
    ("Mellum2-12B-A2.5B-Instruct", 340_349_184),
    ("GLM-4.7-Flash", 591_294_720)])
def test_size_tool_counts_the_decoder_configurations(name, parameters):
    size = _load(os.path.join(ROOT, "benchmark", "tools", "size.py"),
                 "_bench_tools_size")
    cfg = size.read_config(name)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "train_s8192_b4.json")) as f:
        s = size.size(cfg, json.load(f))
    assert s["parameters"] == parameters
    assert s["reference_blocks_a_step"] == 4
    assert s["reference_bytes_in_blocks"] == 12 * parameters
    assert s["reference_bytes_in_step"] == 16 * parameters
    assert s["program_bytes"] == 16 * parameters
