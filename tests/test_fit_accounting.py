"""The training loop's own accounting of a ``fit`` (ISSUE 26).

Three views of one ``fit``, handed out as ``model.last_fit_report``:

* the goodput ledger, which with the loop's in-flight probe as
  ``device_busy`` books a host interval as ``data_wait`` only when the
  device had run dry (starvation), and as ``device_step`` while it still had
  dispatched steps (the host was merely ahead),
* the in-flight depth itself (``zoo_train_inflight_steps``,
  ``zoo_train_dispatch_on_empty_total``),
* the host's phases (``zoo_train_host_seconds_total{phase=}``), each also an
  event of the profiler's ``/host:CPU`` plane,

and beside them what JAX compiled, from its own monitoring events
(``zoo_xla_compile_seconds_total{fn=,phase=}``,
``zoo_xla_compile_total{fn=,cache=}``). The benchmark's six readers
(``benchmark/layer_metrics``) read the report and these counters.
"""

import glob
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common.context import init_zoo_context
from analytics_zoo_tpu.feature import FeatureSet
from analytics_zoo_tpu.observability import (GoodputLedger, MetricsRegistry,
                                             default_registry, instrument_jit,
                                             span)
from analytics_zoo_tpu.observability.compile import (COMPILE_PHASES,
                                                     UNINSTRUMENTED,
                                                     xla_compile_totals)
from analytics_zoo_tpu.observability.goodput import (TRAIN_CATEGORIES,
                                                     InflightProbe)
from analytics_zoo_tpu.observability.tracing import HostPhase
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
from analytics_zoo_tpu.pipeline.api.keras.training import HOST_PHASES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("loop.goodput_share", "loop.host_ahead_steps",
           "loop.call_overhead_share", "ctx.trace_lower_s",
           "ctx.backend_compile_s", "ctx.cache_load_s")


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Loss:
    """What the probe needs of a dispatched segment's loss array."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_ready(self):
        return self.ready


# ---------------------------------------------------------------------------
# the ledger's repaired category (injected clock, stubbed device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("busy,booked", [(True, "device_step"),
                                         (False, "data_wait")])
def test_data_wait_is_booked_only_on_a_dry_device(busy, booked):
    clock = _Clock()
    led = GoodputLedger("train", registry=MetricsRegistry(), clock=clock,
                        device_busy=lambda: busy)
    led.open()
    clock.t = 0.25
    led.note("idle")
    clock.t = 2.25
    assert led.note("data_wait") == 2.0     # the host waited two seconds
    clock.t = 2.5
    led.note("device_step")
    clock.t = 3.0
    led.note("idle")                        # only data_wait asks the device
    sec = led.seconds()
    other = "data_wait" if booked == "device_step" else "device_step"
    assert sec[booked] == (2.25 if booked == "device_step" else 2.0)
    assert sec[other] == (0.0 if other == "data_wait" else 0.25)
    assert sec["idle"] == 0.75
    # the invariant, exact: every second in exactly one category
    assert sum(sec.values()) == led.wall() == 3.0
    assert led.goodput_seconds() + sum(led.badput_seconds().values()) == 3.0
    assert set(sec) == set(TRAIN_CATEGORIES)


def test_ledger_without_a_probe_books_as_told():
    clock = _Clock()
    led = GoodputLedger("train", registry=MetricsRegistry(), clock=clock)
    led.open()
    clock.t = 1.0
    led.note("data_wait")
    assert led.seconds()["data_wait"] == 1.0


# ---------------------------------------------------------------------------
# the in-flight probe
# ---------------------------------------------------------------------------

def test_probe_counts_steps_not_segments_and_skips_a_fits_first_dispatch():
    reg = MetricsRegistry()
    probe = InflightProbe(reg)
    assert probe.summary() == {"median": None, "min": None, "max": None,
                               "dispatch_on_empty": 0}
    assert probe.at_dispatch() == 0         # a fit's first: not "on empty"
    a, b, c = _Loss(), _Loss(), _Loss()
    probe.dispatched(a)
    assert probe.busy()
    assert probe.at_dispatch() == 1
    probe.dispatched(b, steps=4)            # a scan chunk counts its K
    assert probe.at_dispatch() == 5
    probe.dispatched(c)
    a.ready = b.ready = True                # the device finished two segments
    assert probe.at_dispatch() == 1
    c.ready = True
    assert not probe.busy()
    assert probe.at_dispatch() == 0         # ran dry: the loop sets the pace
    assert probe.steps == 6
    assert probe.summary() == {"median": 1.0, "min": 0, "max": 5,
                               "dispatch_on_empty": 1}
    snap = reg.snapshot(compact=True)
    assert snap["zoo_train_inflight_steps"]["count"] == 5
    assert snap["zoo_train_dispatch_on_empty_total"]["value"] == 1
    probe.clear()
    assert not probe.busy()


def test_probe_sweeps_in_dispatch_order():
    """A later segment cannot finish before an earlier one: the sweep stops
    at the first array that is not ready and asks no further."""
    probe = InflightProbe(MetricsRegistry())
    first, second = _Loss(ready=False), _Loss(ready=True)
    probe.dispatched(first)
    probe.dispatched(second)
    assert probe.at_dispatch() == 2


# ---------------------------------------------------------------------------
# host phases and spans on the profiler's clock
# ---------------------------------------------------------------------------

def test_host_phase_adds_its_seconds_to_the_counter():
    reg = MetricsRegistry()
    counter = reg.counter("zoo_train_host_seconds_total",
                          labels={"phase": "data.put"})
    phase = HostPhase("train.data.put", counter)
    for _ in range(3):
        with phase:
            time.sleep(0.01)
    assert 0.03 <= counter.value < 0.5


def _events_of(trace_dir):
    """``{name: count}`` of the ``/host:CPU`` plane of the newest trace."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                seen[ev.name] = seen.get(ev.name, 0) + 1
    return seen


def _data(n=64, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, 1)).astype(np.float32)
    return x, (x @ w).astype(np.float32)


def _model(d=8, hidden=8, layers=1):
    stack = [Dense(hidden, activation="relu", input_shape=(d,))]
    stack += [Dense(hidden, activation="relu") for _ in range(layers - 1)]
    m = Sequential(stack + [Dense(1)])
    m.compile(optimizer="adam", loss="mse", lr=0.01)
    return m


def test_profile_of_a_fit_holds_the_zoo_spans_in_the_host_plane(tmp_path):
    init_zoo_context()
    x, y = _data()
    m = _model()
    m.fit(x, y, batch_size=16, nb_epoch=1)      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        m.fit(x, y, batch_size=16, nb_epoch=2)
        with span("serving.dispatch", registry=MetricsRegistry()):
            pass                                # any span, not only fit's
    finally:
        jax.profiler.stop_trace()
    seen = _events_of(str(tmp_path))
    assert seen.get("train.fit") == 1
    assert seen.get("train.fit.enter") == 1
    assert seen.get("zoo.train.step") == 8      # 2 epochs of 4 steps
    assert seen.get("train.step.dispatch") == 8
    assert seen.get("train.data.put") == 8
    assert seen.get("train.data.pull", 0) >= 8
    assert seen.get("train.epoch.tail") == 2
    assert seen.get("train.epoch.publish") == 2
    assert seen.get("serving.dispatch") == 1


# ---------------------------------------------------------------------------
# one fit, three views: model.last_fit_report
# ---------------------------------------------------------------------------

def test_last_fit_report_reconciles(tmp_path):
    init_zoo_context()
    x, y = _data()
    m = _model()
    assert m.last_fit_report is None
    m.fit(x, y, batch_size=16, nb_epoch=2)
    r = m.last_fit_report
    assert set(r) == {"wall_s", "steps", "ledger", "host_s", "inflight",
                      "compile", "state", "remat_saved_bytes", "mixers"}
    assert r["mixers"] == {}            # no DecoderStack in this model
    assert set(r["state"]) == {"source", "bytes", "published"}
    # no rematerialised DecoderStack in this model: nothing kept
    assert r["remat_saved_bytes"] == {"flash_out": 0, "flash_lse": 0}
    assert r["state"]["published"] == "handed_back" and r["state"]["bytes"] > 0
    assert r["steps"] == 8
    assert set(r["ledger"]) == set(TRAIN_CATEGORIES)
    assert sum(r["ledger"].values()) == pytest.approx(r["wall_s"], rel=1e-9)
    assert set(r["host_s"]) == set(HOST_PHASES)
    assert all(v > 0 for v in r["host_s"].values())
    assert sum(r["host_s"].values()) <= r["wall_s"]
    assert set(r["inflight"]) == {"median", "min", "max",
                                  "dispatch_on_empty"}
    assert 0 <= r["inflight"]["min"] <= r["inflight"]["median"] \
        <= r["inflight"]["max"]
    # the first fit compiled its step: booked under the entry point's name
    step = r["compile"]["train.step"]
    assert step["trace"] > 0 and step["lower"] > 0
    assert step.get("hit", 0) + step.get("miss", 0) == 1
    first_wall = r["wall_s"]

    # the report is a delta over ONE fit: the next one compiled no step
    m.fit(x, y, batch_size=16, nb_epoch=2)
    r2 = m.last_fit_report
    assert r2 is not r and r2["steps"] == 8
    assert "train.step" not in r2["compile"]
    assert r2["wall_s"] < first_wall
    assert sum(r2["ledger"].values()) == pytest.approx(r2["wall_s"],
                                                       rel=1e-9)
    # and it is what the registry's counters hold
    host_total = {dict(c.labels)["phase"]: c.value
                  for c in default_registry().metrics()
                  if c.name == "zoo_train_host_seconds_total"}
    for phase in HOST_PHASES:
        assert host_total[phase] == pytest.approx(
            r["host_s"][phase] + r2["host_s"][phase], rel=1e-9)


def test_report_survives_a_failed_fit_and_an_unaccounted_one():
    init_zoo_context(goodput_enabled=False)
    try:
        x, y = _data()
        m = _model()

        def boom(record):
            raise KeyError("callback failed")

        with pytest.raises(KeyError):
            m.fit(x, y, batch_size=16, nb_epoch=1, callbacks=[boom])
        r = m.last_fit_report
        assert r["steps"] == 4 and r["ledger"] == {}
        assert r["host_s"]["epoch.publish"] > 0     # ended by the exception
        m.fit(x, y, batch_size=16, nb_epoch=1)      # the stack is reusable
        assert m.last_fit_report["steps"] == 4
    finally:
        init_zoo_context()


class _SlowSource(FeatureSet):
    """A FeatureSet whose every batch takes ``delay`` seconds to assemble."""

    delay = 0.0

    def iter_batches(self, batch_size, *, epoch=0, drop_last=True):
        for batch in super().iter_batches(batch_size, epoch=epoch,
                                          drop_last=drop_last):
            time.sleep(self.delay)
            yield batch


def test_fit_over_a_slow_source_reads_starvation():
    """Tiny steps behind a source that sleeps: the chip runs dry before
    every batch, and the ledger and the probe both say so."""
    init_zoo_context()
    x, y = _data(n=96)
    m = _model()
    m.fit(x, y, batch_size=16, nb_epoch=1)          # compile
    fs = _SlowSource(x, y, shuffle=False)
    fs.delay = 0.05
    m.fit(fs, batch_size=16, nb_epoch=1)
    r = m.last_fit_report
    slept = 6 * fs.delay
    assert r["steps"] == 6
    assert r["ledger"]["data_wait"] >= 0.8 * slept
    assert r["host_s"]["data.pull"] >= 0.8 * slept  # and where it waited
    assert r["inflight"]["dispatch_on_empty"] > 0
    assert r["inflight"]["median"] == 0


def test_fit_over_a_fast_source_reads_none():
    """Steps that outlast the host's turn around the loop, from memory: the
    host runs ahead, no dispatch finds the device dry, and what the host
    spends in the input pipeline is not booked as starvation."""
    init_zoo_context()
    d = 512
    x, y = _data(n=3 * 256, d=d)
    m = _model(d=d, hidden=1024, layers=3)
    m.fit(x, y, batch_size=256, nb_epoch=1)         # compile
    # three steps: XLA:CPU's throttle (a sync every 4 dispatches) stays out
    m.fit(x, y, batch_size=256, nb_epoch=1)
    r = m.last_fit_report
    assert r["steps"] == 3
    assert r["inflight"]["dispatch_on_empty"] == 0
    assert r["inflight"]["max"] >= 1
    # all that is left under data_wait is the pipeline's first fill, before
    # anything was dispatched; the steps and the drain are device_step
    assert r["ledger"]["data_wait"] < 0.2 * r["wall_s"]
    assert r["ledger"]["device_step"] > 0.5 * r["wall_s"]


# ---------------------------------------------------------------------------
# compile accounting from JAX's own events
# ---------------------------------------------------------------------------

def _seconds(totals, fn):
    return {p: totals.get(fn, {}).get(p, 0.0) for p in COMPILE_PHASES}


def test_compile_seconds_are_booked_to_the_entry_point_on_the_stack():
    def inner(v):
        return jnp.tanh(v) @ v

    def outer(v):
        out = v
        for _ in range(3):      # nested jits: each traced inside the outer
            out = jax.jit(inner)(out) + jnp.mean(out)
        return out

    f = instrument_jit(outer, name="test.outer")
    x = jnp.ones((16, 16))
    before = xla_compile_totals()
    t0 = time.perf_counter()
    f(x).block_until_ready()
    wall = time.perf_counter() - t0
    # the PR 22 program: an eager reduction whose shape holds a step count
    float(jnp.mean(jnp.concatenate([jnp.atleast_1d(x[0, 0])
                                    for _ in range(7)])))
    after = xla_compile_totals()
    mine = {p: _seconds(after, "test.outer")[p]
            - _seconds(before, "test.outer")[p] for p in COMPILE_PHASES}
    assert mine["trace"] > 0 and mine["lower"] > 0
    assert mine["backend"] + mine["cache_load"] > 0
    # nested traces are reported inside their caller's duration too: each
    # second is booked once, so the phases fit inside the call
    assert sum(mine.values()) <= wall
    assert after["test.outer"].get("hit", 0) \
        + after["test.outer"].get("miss", 0) == 1
    other = {p: _seconds(after, UNINSTRUMENTED)[p]
             - _seconds(before, UNINSTRUMENTED)[p] for p in COMPILE_PHASES}
    assert other["trace"] > 0 and other["lower"] > 0
    assert other["backend"] + other["cache_load"] > 0

    # a second call compiles nothing, and books nothing
    f(x).block_until_ready()
    assert xla_compile_totals()["test.outer"] == after["test.outer"]


def test_jit_compile_event_carries_the_phase_durations():
    events = []

    class Sink:
        def write(self, event):
            events.append(event)

    reg = MetricsRegistry()
    reg.add_event_sink(Sink())
    f = instrument_jit(lambda v: v * 2 + 1, name="test.phases", registry=reg)
    f(jnp.ones((4,))).block_until_ready()
    (ev,) = [e for e in events if e["kind"] == "jit.compile"]
    assert ev["fn"] == "test.phases"
    assert ev["trace_s"] > 0 and ev["lower_s"] > 0
    assert ev["backend_s"] + ev["cache_load_s"] > 0
    assert ev["trace_s"] + ev["lower_s"] + ev["backend_s"] \
        + ev["cache_load_s"] <= ev["dur_s"]


@pytest.fixture
def scratch_compile_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own, taking
    programs of any size; the process's settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], -1)
    yield
    cc.reset_cache()
    for n, v in before.items():
        jax.config.update(n, v)


def test_warm_cache_books_a_hit_and_no_backend_seconds(
        scratch_compile_cache):
    def program(v):
        return jnp.sin(v) @ v.T + 26.0

    x = jnp.ones((8, 8))
    instrument_jit(program, name="test.cached")(x).block_until_ready()
    cold = xla_compile_totals()["test.cached"]
    assert cold["miss"] == 1 and cold["backend"] > 0
    assert "hit" not in cold and "cache_load" not in cold

    jax.clear_caches()          # a new process, as far as JAX's memory goes
    instrument_jit(program, name="test.cached")(x).block_until_ready()
    warm = xla_compile_totals()["test.cached"]
    assert warm["hit"] == 1 and warm["miss"] == 1
    assert warm["cache_load"] > 0
    assert warm["backend"] == cold["backend"]       # no XLA this time
    assert warm["trace"] > cold["trace"]            # Python traced again


# ---------------------------------------------------------------------------
# the benchmark's readers
# ---------------------------------------------------------------------------

def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", READERS)
def test_benchmark_reader_reads_the_report(name):
    init_zoo_context()
    x, y = _data()
    m = _model()
    m.fit(x, y, batch_size=16, nb_epoch=1)
    value = _reader(name)({"model": m})
    assert isinstance(value, float) and value >= 0.0
    r = m.last_fit_report
    if name == "loop.goodput_share":
        assert value == 100.0 * r["ledger"]["device_step"] / r["wall_s"]
    elif name == "loop.host_ahead_steps":
        assert value == r["inflight"]["median"]
    elif name == "loop.call_overhead_share":
        assert 0.0 < value <= 100.0
    elif name == "ctx.trace_lower_s":
        step = xla_compile_totals()["train.step"]
        assert value == pytest.approx(step["trace"] + step["lower"])


@pytest.mark.parametrize("name", READERS[:3])
def test_benchmark_reader_finds_nothing_on_a_program_without_the_report(
        name):
    """Laid over the parent's checkout the readers return nothing and do
    not raise: its model has no ``last_fit_report``."""

    class ParentModel:
        pass

    assert _reader(name)({"model": ParentModel()}) is None
