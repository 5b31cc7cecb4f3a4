"""Goodput accounting + alert-triggered profiler capture
(``observability/goodput.py`` / ``observability/profiler.py``).

The contract under test (docs/guides/OBSERVABILITY.md "Goodput &
performance attribution"):

* **exclusive, exhaustive attribution** — every second between
  ``open()`` and the last ``note()`` lands in exactly one category, so
  ``goodput + Σ badput == wall time`` reconciles exactly, including
  under an injected fault plan that forces a rollback, a supervised
  restart, replay skips, and checkpoint latency in ONE fit,
* **registry surfaces agree** — the ledger object, the exported
  counter/gauge families, and ``registry_snapshot`` tell one story,
* **alert → capture** — a rule entering ``firing`` arms exactly one
  bounded capture (at most one in flight; trace dirs reconcile 1:1
  with ``zoo_profile_captures_total``; retention evicts the oldest),
* **capture failure is contained** — the ``profiler.capture`` fault
  site degrades to a counter bump + event, never an exception into the
  hosting loop,
* **operator surfaces** — ``/statusz`` carries the ``performance``
  block, ``POST /profilez`` arms over HTTP, and the goodput column
  rolls up through ``zoo-fleet check`` / ``cluster-serving-status``.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.common import faults
from analytics_zoo_tpu.common.context import init_zoo_context
from analytics_zoo_tpu.common.faults import FaultPlan
from analytics_zoo_tpu.observability import (AlertEngine, AlertRule,
                                             GoodputLedger, MetricsRegistry,
                                             ProfilerTrigger, ScrapeServer,
                                             StoreSignals, TimeSeriesStore,
                                             default_registry,
                                             default_ruleset)
from analytics_zoo_tpu.observability.goodput import registry_snapshot
from analytics_zoo_tpu.pipeline.api.keras import Sequential
from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

BATCH = 32


# ---------------------------------------------------------------------------
# ledger exactness (injected clock — deterministic to the float)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_ledger_exclusive_attribution_reconciles_exactly():
    """Interval attribution with a hand-driven clock: every category
    gets exactly the seconds the sequence says, the invariant holds to
    the float, and the exported families mirror the ledger."""
    reg = MetricsRegistry()
    clk = _Clock()
    led = GoodputLedger("train", registry=reg, clock=clk)
    led.open()
    for dt, cat in ((2.0, "compile"), (0.5, "data_wait"),
                    (4.0, "device_step"), (0.25, "ckpt_stall"),
                    (1.0, "device_step"), (0.25, "idle")):
        clk.t += dt
        assert led.note(cat) == dt
    sec = led.seconds()
    assert sec == {"device_step": 5.0, "data_wait": 0.5, "compile": 2.0,
                   "ckpt_stall": 0.25, "rollback_replay": 0.0,
                   "restart": 0.0, "anomaly_skip": 0.0, "idle": 0.25}
    assert led.wall() == 8.0
    assert led.goodput_seconds() == 5.0
    assert led.goodput_seconds() + sum(led.badput_seconds().values()) \
        == led.wall()
    assert led.ratio() == 5.0 / 8.0
    # the registry tells the same story, family by family
    snap = reg.snapshot(compact=True)
    assert snap["zoo_goodput_ratio"]["value"] == 5.0 / 8.0
    assert snap["zoo_goodput_seconds_total"]["value"] == 5.0
    assert snap['zoo_badput_seconds_total{category="compile"}']["value"] \
        == 2.0
    assert snap['zoo_badput_seconds_total{category="data_wait"}']["value"] \
        == 0.5
    rs = registry_snapshot(reg)
    assert rs["ratio"] == 5.0 / 8.0 and rs["goodput_s"] == 5.0
    assert rs["badput_s"]["ckpt_stall"] == 0.25
    assert sum(rs["badput_s"].values()) + rs["goodput_s"] == led.wall()


def test_ledger_edges():
    """Unknown categories refuse loudly; the first note of an unopened
    ledger only arms the mark (no phantom interval); reopen keeps the
    accumulated seconds (a retry continues the same run's ledger); a
    fresh registry reads back as ratio=None, not a fake 0."""
    reg = MetricsRegistry()
    clk = _Clock()
    led = GoodputLedger("serve", registry=reg, clock=clk)
    with pytest.raises(ValueError, match="unknown category"):
        led.note("device_step")     # a TRAIN category, wrong role
    clk.t = 5.0
    assert led.note("idle") == 0.0  # unopened: arms the mark only
    clk.t = 6.0
    assert led.note("device_dispatch") == 1.0
    led.open()                      # re-arm across a gap
    clk.t = 10.0                    # open() read t=6.0 … make the gap real
    led.open(now=9.0)
    clk.t = 10.0
    assert led.note("publish") == 1.0
    assert led.wall() == 2.0        # the 6.0→9.0 gap was never attributed
    assert registry_snapshot(MetricsRegistry()) \
        == {"ratio": None, "goodput_s": 0.0, "badput_s": {}}


# ---------------------------------------------------------------------------
# hbm_high_watermark — the new default-ruleset page
# ---------------------------------------------------------------------------

def test_hbm_high_watermark_rule_fires_on_fraction_of_limit():
    """in_use/limit above 0.92 pages; below it, or with no HBM gauges
    at all (CPU host), the rule reads no-data/healthy and stays quiet."""
    rule = next(r for r in default_ruleset(for_s=0.0)
                if r.name == "hbm_high_watermark")
    assert rule.severity == "page"
    store = TimeSeriesStore(retention_s=60.0, sample_interval_s=1.0)
    sig = StoreSignals(store, clock=lambda: 10.0)
    eng = AlertEngine([rule], registry=MetricsRegistry(),
                      clock=lambda: 10.0)
    eng.evaluate(sig, now=10.0)     # no gauges: no data, no page
    assert eng.state("hbm_high_watermark") == "inactive"
    lim = 16.0e9
    for dev in ("0", "1"):
        store.record(f'zoo_device_hbm_bytes{{device="{dev}",kind="limit"}}',
                     "gauge", 10.0, lim)
        store.record(f'zoo_device_hbm_bytes{{device="{dev}",kind="in_use"}}',
                     "gauge", 10.0, 0.5 * lim)
    eng.evaluate(sig, now=10.0)
    assert eng.state("hbm_high_watermark") == "inactive"   # 50%: fine
    store.record('zoo_device_hbm_bytes{device="0",kind="in_use"}',
                 "gauge", 11.0, 0.99 * lim)
    store.record('zoo_device_hbm_bytes{device="1",kind="in_use"}',
                 "gauge", 11.0, 0.93 * lim)
    eng.evaluate(sig, now=11.0)     # fleet fraction 96% > 92%
    assert eng.state("hbm_high_watermark") == "firing"


# ---------------------------------------------------------------------------
# chaos fit: the full badput taxonomy in one run, reconciled
# ---------------------------------------------------------------------------

def _data(n=256, d=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, 1)).astype(np.float32)
    return x, (x @ w).astype(np.float32)


def _model(lr=0.05):
    m = Sequential([Dense(8, activation="relu", input_shape=(8,)),
                    Dense(1)])
    m.compile(optimizer="adam", loss="mse", lr=lr)
    return m


def _family_totals(*names):
    """Default-registry per-family totals (labeled series summed into
    ``name{k="v"}`` keys), absent -> 0.0 — tests diff before/after."""
    snap = default_registry().snapshot(compact=True)
    out = {}
    for n in names:
        for key, entry in snap.items():
            if key == n or key.startswith(n + "{"):
                out[key] = entry.get("value", entry.get("count", 0.0))
        out.setdefault(n, 0.0)
    return out


def test_chaos_fit_goodput_reconciles_to_wall_time(tmp_path):
    """One fit through the whole failure taxonomy — 3 poisoned steps
    (> skip budget 2 ⇒ one rollback + replay skips), a checkpoint save
    killed mid-write (⇒ one supervised restart), and injected manifest
    latency (⇒ checkpoint stall) — and the ledger still attributes
    every second exclusively: goodput + Σ badput == wall, the exported
    families' deltas match the ledger per category, and every expected
    badput category is charged."""
    init_zoo_context(faults_enabled=True, train_sentinel="recover",
                     train_max_skips_per_epoch=2)
    x, y = _data()
    m = _model()
    m.set_checkpoint(str(tmp_path / "ckpt"))
    m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)  # clean ckpt-8

    fams = ("zoo_goodput_seconds_total", "zoo_badput_seconds_total",
            "zoo_train_rollback_total")
    before = _family_totals(*fams)
    # epoch 2's dispatches are site calls 0..7: batches 2,3,4 poisoned →
    # 3 skips > budget 2 ⇒ rollback to ckpt-8 + replay with skips; the
    # replayed epoch's save then dies at its first tree file ⇒ the
    # failure surfaces at the next save and the retry loop restarts;
    # every manifest commit pays injected latency ⇒ visible ckpt_stall
    plan = (FaultPlan(seed=11)
            .add("train.grads", "nan_loss", at=(2, 3, 4))
            .add("ckpt.write", "error", at=(0,))
            .add("ckpt.manifest", "latency", delay_s=0.02,
                 at=(0, 1, 2, 3, 4, 5)))
    with faults.activate(plan):
        m.fit(x, y, batch_size=BATCH, nb_epoch=2, shuffle=False)
    after = _family_totals(*fams)

    assert len(plan.fired_at("train.grads")) == 3
    assert len(plan.fired_at("ckpt.write")) == 1
    assert after["zoo_train_rollback_total"] \
        - before["zoo_train_rollback_total"] == 1

    led = m._loop._goodput
    sec = led.seconds()
    # the invariant: exclusive and exhaustive, no unaccounted bucket
    assert led.goodput_seconds() + sum(led.badput_seconds().values()) \
        == pytest.approx(led.wall(), rel=1e-12)
    assert sum(sec.values()) == pytest.approx(led.wall(), rel=1e-12)
    assert led.ratio() == pytest.approx(
        sec["device_step"] / led.wall(), rel=1e-12)
    # every failure mode the plan forced left its wall-time fingerprint
    # (data_wait: these steps are microseconds long, so the device has run
    # dry by the end of most of the host's turns through the input
    # pipeline; the ledger asks the loop's in-flight probe at each)
    for cat in ("device_step", "data_wait", "ckpt_stall",
                "rollback_replay", "restart", "anomaly_skip", "idle"):
        assert sec[cat] > 0.0, f"category {cat} never charged"
    # the per-fit report hands out the same ledger, through the rollback
    # and the restart: one fit, one wall clock
    report = m.last_fit_report
    assert report["ledger"] == sec
    assert report["wall_s"] == pytest.approx(led.wall(), rel=1e-9)
    assert sum(report["host_s"].values()) <= report["wall_s"]
    # the manifest latency DID fire — but on the background writer
    # thread, so the ledger charges only the synchronous join window:
    # async-hidden save time is by design not badput
    assert len(plan.fired_at("ckpt.manifest")) >= 1
    # the exported families moved by exactly this fit's ledger
    assert after["zoo_goodput_seconds_total"] \
        - before["zoo_goodput_seconds_total"] \
        == pytest.approx(sec["device_step"], rel=1e-9)
    for cat, s in led.badput_seconds().items():
        key = f'zoo_badput_seconds_total{{category="{cat}"}}'
        assert after.get(key, 0.0) - before.get(key, 0.0) \
            == pytest.approx(s, rel=1e-9, abs=1e-12), cat
    # the registry roll-up recomputes its ratio from the SUMMED seconds
    # (several ledgers — the clean fit above and this one — exported into
    # the default registry; the last-writer gauge would misstate that)
    snap = registry_snapshot()
    wall_all = snap["goodput_s"] + sum(snap["badput_s"].values())
    assert snap["ratio"] == pytest.approx(snap["goodput_s"] / wall_all,
                                          rel=1e-12)


def test_goodput_disabled_leaves_no_ledger(tmp_path):
    init_zoo_context(goodput_enabled=False)
    try:
        x, y = _data(n=64)
        m = _model()
        m.fit(x, y, batch_size=BATCH, nb_epoch=1, shuffle=False)
        assert m._loop._goodput is None
    finally:
        init_zoo_context()


# ---------------------------------------------------------------------------
# alert → capture lifecycle
# ---------------------------------------------------------------------------

class _FakeProfiler:
    def __init__(self):
        self.started, self.stopped = [], 0

    def start(self, d):
        self.started.append(d)

    def stop(self):
        self.stopped += 1


def test_alert_transition_arms_exactly_one_capture(tmp_path):
    """A rule crossing into firing arms ONE capture through the
    transition hook; while it is in flight further transitions and
    manual arms are refused; the counter, the trace dirs, and the fake
    profiler's start calls reconcile 1:1."""
    reg = MetricsRegistry()
    events = []
    reg.add_event_sink(type("S", (), {
        "write": lambda self, r: events.append(r),
        "close": lambda self: None})())
    fake = _FakeProfiler()
    trig = ProfilerTrigger(str(tmp_path / "prof"), registry=reg, keep=10,
                           duration_s=0.0, steps=0,
                           start_fn=fake.start, stop_fn=fake.stop)
    rule = AlertRule("depth_high", lambda s: s.v, threshold=10.0,
                     for_s=5.0, severity="page", summary="backlog")
    eng = AlertEngine([rule], registry=reg, clock=lambda: 0.0)
    eng.add_transition_hook(trig.on_alert)
    sig = type("V", (), {"v": 50.0})()
    eng.evaluate(sig, now=0.0)                 # pending — no capture
    assert fake.started == [] and trig.in_flight() is None
    eng.evaluate(sig, now=6.0)                 # firing — one capture
    flight = trig.in_flight()
    assert flight is not None and flight["trigger"] == "alert"
    assert fake.started == [flight["dir"]]
    assert os.path.isdir(flight["dir"])
    # a second arm (any source) is refused while one is in flight
    assert trig.arm("manual") is None
    assert fake.started == [flight["dir"]]
    snap = reg.snapshot(compact=True)
    assert snap['zoo_profile_captures_total{trigger="alert"}']["value"] == 1
    assert snap['zoo_profile_captures_total{trigger="manual"}']["value"] == 0
    assert trig.stop() == flight["dir"] and fake.stopped == 1
    assert trig.stop() is None and fake.stopped == 1   # idempotent
    phases = [e.get("phase") for e in events
              if e.get("kind") == "profile.capture"]
    assert phases == ["start", "skipped", "stop"]
    # resolve → re-fire arms a SECOND capture (new episode, new trace)
    sig.v = 1.0
    eng.evaluate(sig, now=7.0)
    sig.v = 50.0
    eng.evaluate(sig, now=8.0)
    eng.evaluate(sig, now=20.0)
    assert len(fake.started) == 2
    snap = reg.snapshot(compact=True)
    assert snap['zoo_profile_captures_total{trigger="alert"}']["value"] == 2


def test_step_bound_and_retention_eviction(tmp_path):
    """A steps-bounded capture stops itself after N step() calls;
    retention keeps only the newest ``keep`` capture dirs and never the
    in-flight one."""
    reg = MetricsRegistry()
    fake = _FakeProfiler()
    trig = ProfilerTrigger(str(tmp_path / "prof"), registry=reg, keep=2,
                           duration_s=0.0, steps=3,
                           start_fn=fake.start, stop_fn=fake.stop)
    d1 = trig.arm("manual")
    assert d1 is not None
    trig.step(); trig.step()
    assert trig.in_flight() is not None        # budget not yet spent
    trig.step()
    assert trig.in_flight() is None and fake.stopped == 1
    d2 = trig.arm("http")
    trig.step(); trig.step(); trig.step()
    d3 = trig.arm("manual")
    for _ in range(3):
        trig.step()
    d4 = trig.arm("manual")                    # eviction runs on each arm
    names = sorted(os.listdir(str(tmp_path / "prof")))
    assert names == [os.path.basename(d) for d in (d3, d4)]
    assert d1 is not None and d2 is not None and d4 is not None
    trig.close()


def test_profiler_capture_fault_degrades_gracefully(tmp_path):
    """The ``profiler.capture`` chaos site: an injected error makes
    ``arm()`` return None, bump the failure counter, and emit a
    ``phase="failed"`` event — nothing escapes into the caller, and the
    next arm (plan exhausted) succeeds. Reconciled exactly against
    ``plan.fired``."""
    init_zoo_context(faults_enabled=True)
    reg = MetricsRegistry()
    events = []
    reg.add_event_sink(type("S", (), {
        "write": lambda self, r: events.append(r),
        "close": lambda self: None})())
    fake = _FakeProfiler()
    trig = ProfilerTrigger(str(tmp_path / "prof"), registry=reg,
                           duration_s=0.0, steps=0,
                           start_fn=fake.start, stop_fn=fake.stop)
    plan = FaultPlan(seed=5).add("profiler.capture", "error", at=(0,))
    with faults.activate(plan):
        assert trig.arm("alert", reason="chaos") is None   # injected fail
        d = trig.arm("alert")                              # recovers
    assert plan.fired == [("profiler.capture", "error", 0)]
    assert d is not None and fake.started == [d]
    snap = reg.snapshot(compact=True)
    assert snap["zoo_profile_capture_failures_total"]["value"] == 1
    assert snap['zoo_profile_captures_total{trigger="alert"}']["value"] == 1
    failed = [e for e in events if e.get("phase") == "failed"]
    assert len(failed) == 1 and "FaultError" in failed[0]["error"]
    trig.close()


# ---------------------------------------------------------------------------
# HTTP surfaces: /statusz performance block + POST /profilez
# ---------------------------------------------------------------------------

def test_statusz_performance_and_profilez_http(tmp_path):
    """Live HTTP: ``/statusz`` carries the goodput roll-up + profiler
    state; ``POST /profilez`` arms (200), refuses a second in-flight
    capture (409), and 404s with no profiler mounted."""
    reg = MetricsRegistry()
    clk = _Clock()
    led = GoodputLedger("serve", registry=reg, clock=clk)
    led.open()
    clk.t = 3.0
    led.note("device_dispatch")
    clk.t = 4.0
    led.note("idle")
    fake = _FakeProfiler()
    trig = ProfilerTrigger(str(tmp_path / "prof"), registry=reg,
                           duration_s=0.0, steps=0,
                           start_fn=fake.start, stop_fn=fake.stop)
    srv = ScrapeServer(reg, port=0, profiler=trig)
    base = f"http://{srv.host}:{srv.port}"
    try:
        with urllib.request.urlopen(base + "/statusz", timeout=10.0) as r:
            status = json.loads(r.read())
        perf = status["performance"]
        assert perf["ratio"] == 0.75
        assert perf["goodput_s"] == 3.0 and perf["badput_s"]["idle"] == 1.0
        assert perf["profiler"] == {"in_flight": None,
                                    "trace_dir": trig.trace_dir}
        req = urllib.request.Request(base + "/profilez", data=b"",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=10.0) as r:
            armed = json.loads(r.read())
        assert armed["armed"] is True and fake.started == [armed["dir"]]
        assert armed["in_flight"]["trigger"] == "http"
        with pytest.raises(urllib.error.HTTPError) as e2:
            urllib.request.urlopen(
                urllib.request.Request(base + "/profilez", data=b"",
                                       method="POST"), timeout=10.0)
        assert e2.value.code == 409               # already in flight
        assert json.loads(e2.value.read())["armed"] is False
        with urllib.request.urlopen(base + "/statusz", timeout=10.0) as r:
            flight = json.loads(r.read())["performance"]["profiler"]
        assert flight["in_flight"]["dir"] == armed["dir"]
    finally:
        trig.close()
        srv.close()
    # no profiler mounted → /profilez is a clean 404, not a crash
    srv2 = ScrapeServer(reg, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as e3:
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://{srv2.host}:{srv2.port}/profilez",
                    data=b"", method="POST"), timeout=10.0)
        assert e3.value.code == 404
    finally:
        srv2.close()


# ---------------------------------------------------------------------------
# CLI roll-up: the goodput column
# ---------------------------------------------------------------------------

def _cli_env():
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(scripts) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    return scripts, env


def test_cli_goodput_column_rolls_up(tmp_path):
    """Subprocess truth: a replica whose scaling block reports goodput
    shows it in ``zoo-fleet check``'s table and in
    ``cluster-serving-status``'s scaling + performance lines."""
    scripts, env = _cli_env()
    reg = MetricsRegistry()
    reg.counter("zoo_serving_records_total", "t").inc(5)
    clk = _Clock()
    led = GoodputLedger("serve", registry=reg, clock=clk)
    led.open()
    clk.t = 17.0
    led.note("device_dispatch")
    clk.t = 20.0
    led.note("publish")                        # ratio 0.85
    scaling = {"consumer": "c-1", "stream_depth": 0, "pending_entries": 0,
               "utilization": 0.5, "batch_size_target": 4,
               "goodput": round(led.ratio(), 4)}
    srv = ScrapeServer(reg, port=0,
                       health_fn=lambda: {"serving": {"running": True,
                                                      "scaling": scaling}})
    try:
        live = f"{srv.host}:{srv.port}"
        r = subprocess.run(
            [sys.executable, os.path.join(scripts, "zoo-fleet"),
             "check", live],
            capture_output=True, text=True, env=env, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "goodput" in r.stdout            # the column header
        row = next(l for l in r.stdout.splitlines() if live in l)
        assert "85%" in row
        r = subprocess.run(
            [sys.executable,
             os.path.join(scripts, "cluster-serving-status"), live],
            capture_output=True, text=True, env=env, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "goodput 85%" in r.stdout        # the scaling line
        perf = next(l for l in r.stdout.splitlines()
                    if l.startswith("performance"))
        assert "goodput 85%" in perf and "publish 3.0s" in perf
    finally:
        srv.close()
