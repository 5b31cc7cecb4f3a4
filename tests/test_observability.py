"""Unified metrics + tracing (``analytics_zoo_tpu/observability``): metric
primitives, exposition-format round-trips, JSON event schema stability
under concurrent writers, span nesting, and the end-to-end reconciliation
smoke tests — after a serving run the Prometheus scrape and the JSON event
log must independently agree with ground truth, and a ``fit`` run must
report a nonzero step-time histogram and throughput gauge."""

import json
import math
import threading
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu import observability as obs
from analytics_zoo_tpu.common.context import init_zoo_context
from analytics_zoo_tpu.observability.metrics import _EXP_LO

# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_counter_monotonic_and_typed():
    r = obs.MetricsRegistry()
    c = r.counter("zoo_x_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same object; a kind clash raises
    assert r.counter("zoo_x_total") is c
    with pytest.raises(TypeError):
        r.gauge("zoo_x_total")


def test_gauge_set_add():
    g = obs.MetricsRegistry().gauge("zoo_depth")
    g.set(7)
    g.add(-2)
    assert g.value == 5


def test_histogram_buckets_and_weighted_observe():
    h = obs.MetricsRegistry().histogram("zoo_lat_seconds")
    h.observe(0.75)          # bucket le=1
    h.observe(1.0)           # exact power of two sits on its OWN edge (le=1)
    h.observe(1.5, n=3)      # bucket le=2, weighted
    h.observe(0.0)           # degenerate: first bucket
    assert h.count == 6
    assert h.sum == pytest.approx(0.75 + 1.0 + 3 * 1.5)
    cum = h.cumulative()
    # cumulative counts are monotone and end at (+Inf, count)
    assert all(c1 <= c2 for (_, c1), (_, c2) in zip(cum, cum[1:]))
    assert cum[-1] == (math.inf, 6)
    by_le = dict(cum)
    assert by_le[1.0] == 3      # 0.75 + 1.0 + the zero (clamped low)
    assert by_le[2.0] == 6      # + the three weighted 1.5s


def test_histogram_extremes_clamp_not_crash():
    h = obs.MetricsRegistry().histogram("zoo_x")
    h.observe(1e-300)
    h.observe(1e300)
    h.observe(float("nan"))
    h.observe(-5.0)
    assert h.count == 4
    # clamped into the fixed ladder: first bucket holds the tiny/NaN/neg
    assert h.cumulative()[0][1] >= 3
    assert h.cumulative()[0][0] == pytest.approx(2.0 ** _EXP_LO)


def test_quantile_digest_accuracy_and_merge():
    """The fixed-budget digest stays within ~2% of true quantiles on a
    known distribution, merges losslessly enough to keep that bound, and
    its quantile function is monotone (p99 >= p50 by construction)."""
    import random

    rnd = random.Random(7)
    vals = [rnd.random() for _ in range(20000)]
    d = obs.QuantileDigest(budget=128)
    for v in vals:
        d.add(v)
    svals = sorted(vals)
    for q in (0.5, 0.95, 0.99):
        true = svals[int(q * len(svals))]
        assert abs(d.quantile(q) - true) < 0.02, q
    assert d.quantile(0.5) <= d.quantile(0.95) <= d.quantile(0.99)
    assert d.count == len(vals)
    assert d.sum == pytest.approx(sum(vals))

    # merge: two half-digests rejoin to the same answers
    a, b = obs.QuantileDigest(128), obs.QuantileDigest(128)
    for v in vals[:10000]:
        a.add(v)
    for v in vals[10000:]:
        b.add(v)
    a.merge(b)
    assert a.count == len(vals)
    for q in (0.5, 0.99):
        assert abs(a.quantile(q) - d.quantile(q)) < 0.02

    empty = obs.QuantileDigest()
    assert math.isnan(empty.quantile(0.5))


def test_summary_metric_and_prometheus_roundtrip():
    """Summary → exposition → parse: quantile series carry the
    {quantile=...} label, _sum/_count reconcile, and p99 >= p50 holds in
    the scrape."""
    r = obs.MetricsRegistry()
    s = r.summary("zoo_lat_quantiles_seconds", "latency quantiles")
    for i in range(1, 101):
        s.observe(i / 1000.0)
    with pytest.raises(TypeError):
        r.histogram("zoo_lat_quantiles_seconds")   # kind clash still raises
    parsed = obs.parse_prometheus(obs.render_prometheus(r))
    fam = parsed["zoo_lat_quantiles_seconds"]
    assert fam["type"] == "summary"
    qs = {lab["quantile"]: v for name, lab, v in fam["samples"]
          if name == "zoo_lat_quantiles_seconds"}
    assert set(qs) == {"0.5", "0.95", "0.99"}
    assert qs["0.5"] <= qs["0.95"] <= qs["0.99"]
    assert qs["0.5"] == pytest.approx(0.0505, rel=0.05)
    count = next(v for name, _, v in fam["samples"]
                 if name.endswith("_count"))
    total = next(v for name, _, v in fam["samples"]
                 if name.endswith("_sum"))
    assert count == 100
    assert total == pytest.approx(sum(i / 1000.0 for i in range(1, 101)))
    # snapshot keeps the quantiles in BOTH forms (bench embeds compact)
    snap = r.snapshot(compact=True)["zoo_lat_quantiles_seconds"]
    assert snap["type"] == "summary" and set(snap["quantiles"]) == \
        {"0.5", "0.95", "0.99"}
    # an EMPTY summary must snapshot to strict JSON (no bare NaN): the
    # BENCH record embeds this dict and jq/JSON.parse reject NaN
    r2 = obs.MetricsRegistry()
    r2.summary("zoo_empty_quantiles_seconds")
    empty = r2.snapshot(compact=True)["zoo_empty_quantiles_seconds"]
    assert empty["count"] == 0 and empty["quantiles"] == {}
    json.loads(json.dumps(r2.snapshot(compact=True),
                          allow_nan=False))   # raises on any NaN leak


def test_labeled_metrics_are_distinct_series():
    r = obs.MetricsRegistry()
    a = r.counter("zoo_ops_total", labels={"op": "read"})
    b = r.counter("zoo_ops_total", labels={"op": "write"})
    a.inc(3)
    b.inc(4)
    snap = r.snapshot()
    assert snap['zoo_ops_total{op="read"}']["value"] == 3
    assert snap['zoo_ops_total{op="write"}']["value"] == 4


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip (satellite: minimal-parser round-trip)
# ---------------------------------------------------------------------------


def _populated_registry():
    r = obs.MetricsRegistry()
    r.counter("zoo_served_total", "records served").inc(42)
    r.gauge("zoo_stream_depth", "backlog").set(3)
    h = r.histogram("zoo_wait_seconds", "queue wait")
    for v in (1e-4, 2e-4, 0.01, 0.5, 0.5, 4.0):
        h.observe(v)
    r.histogram("zoo_span_seconds", labels={"span": 'a"b\\c'}).observe(0.1)
    return r


def test_prometheus_roundtrip_names_types_values():
    r = _populated_registry()
    parsed = obs.parse_prometheus(obs.render_prometheus(r))
    assert parsed["zoo_served_total"]["type"] == "counter"
    assert parsed["zoo_stream_depth"]["type"] == "gauge"
    assert parsed["zoo_wait_seconds"]["type"] == "histogram"
    (_, _, v), = [s for s in parsed["zoo_served_total"]["samples"]]
    assert v == 42
    (_, _, d), = parsed["zoo_stream_depth"]["samples"]
    assert d == 3


def test_prometheus_roundtrip_histogram_bucket_monotonicity():
    r = _populated_registry()
    parsed = obs.parse_prometheus(obs.render_prometheus(r))
    samples = parsed["zoo_wait_seconds"]["samples"]
    buckets = [(float(lab["le"].replace("+Inf", "inf")), v)
               for name, lab, v in samples if name.endswith("_bucket")]
    les = [le for le, _ in buckets]
    counts = [c for _, c in buckets]
    assert les == sorted(les) and les[-1] == math.inf
    assert counts == sorted(counts), "cumulative counts must be monotone"
    count = next(v for name, _, v in samples if name.endswith("_count"))
    total = next(v for name, _, v in samples if name.endswith("_sum"))
    assert counts[-1] == count == 6
    assert total == pytest.approx(1e-4 + 2e-4 + 0.01 + 0.5 + 0.5 + 4.0)


def test_prometheus_label_escaping_roundtrip():
    r = _populated_registry()
    parsed = obs.parse_prometheus(obs.render_prometheus(r))
    labels = [lab for name, lab, _ in parsed["zoo_span_seconds"]["samples"]
              if name.endswith("_count")]
    assert labels and labels[0]["span"] == 'a"b\\c'


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        obs.parse_prometheus("this is { not exposition\n")


def test_prometheus_closing_brace_in_label_value_roundtrips():
    """'}' inside a quoted label value is legal exposition — the parser
    must not end the label block at it."""
    r = obs.MetricsRegistry()
    r.counter("zoo_ops_total", labels={"span": "phase}x"}).inc(2)
    parsed = obs.parse_prometheus(obs.render_prometheus(r))
    (_, labels, v), = parsed["zoo_ops_total"]["samples"]
    assert labels["span"] == "phase}x" and v == 2


def test_json_sink_write_after_close_is_dropped_not_raised(tmp_path):
    """A concurrent emitter can race close() (the registry snapshots its
    sink list before removal) — the write must drop, not crash the
    instrumented thread."""
    sink = obs.JsonEventSink(str(tmp_path / "e.jsonl"))
    sink.write({"ts": 0.0, "kind": "a"})
    sink.close()
    sink.write({"ts": 1.0, "kind": "b"})    # must not raise
    assert [e["kind"] for e in obs.read_events(str(tmp_path / "e.jsonl"))] \
        == ["a"]


def test_json_events_visible_before_close(tmp_path):
    """Line-buffered: an operator tailing the log sees events while the
    process is live, and a crash loses at most the in-flight line."""
    path = str(tmp_path / "live.jsonl")
    sink = obs.JsonEventSink(path)
    sink.write({"ts": 0.0, "kind": "live"})
    assert obs.read_events(path), "event not on disk before close()"
    sink.close()


def test_json_sink_size_rotation_bounds_segments(tmp_path):
    """``max_bytes`` rotation: the active file is atomically renamed to
    ``path.1``, older segments shift up, at most ``keep`` survive — so
    total disk stays bounded while :func:`obs.read_events` still returns
    one chronological stream across the whole chain."""
    import os
    path = str(tmp_path / "rot.jsonl")
    sink = obs.JsonEventSink(path, max_bytes=200, keep=2)
    for i in range(50):
        sink.write({"ts": float(i), "kind": "tick", "seq": i})
    sink.close()
    segments = sorted(p for p in os.listdir(tmp_path)
                      if p.startswith("rot.jsonl."))
    assert segments == ["rot.jsonl.1", "rot.jsonl.2"]   # keep=2, no more
    for seg in segments:
        assert os.path.getsize(tmp_path / seg) >= 200
    events = obs.read_events(path)
    seqs = [e["seq"] for e in events]
    # a contiguous suffix of the written sequence, newest always kept,
    # oldest dropped with the reaped segments
    assert seqs == list(range(seqs[0], 50))
    assert 0 < len(seqs) < 50


def test_json_sink_rotation_survives_reader_midstream(tmp_path):
    """Rotation under a live writer: every event written is either in
    the chain or dropped-from-the-oldest-end — never torn, never
    duplicated — and a sink without ``max_bytes`` never rotates."""
    import os
    path = str(tmp_path / "norot.jsonl")
    sink = obs.JsonEventSink(path)          # rotation off by default
    for i in range(200):
        sink.write({"ts": float(i), "kind": "tick", "seq": i})
    sink.close()
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("norot.jsonl.")]
    assert [e["seq"] for e in obs.read_events(path)] == list(range(200))


# ---------------------------------------------------------------------------
# JSON events: schema-stable under concurrent writers
# ---------------------------------------------------------------------------


def test_json_events_concurrent_writers_schema_stable(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = obs.JsonEventSink(path)
    reg = obs.MetricsRegistry()
    reg.add_event_sink(sink)
    n_threads, n_events = 8, 200

    def worker(tid):
        for i in range(n_events):
            if i % 2:
                reg.emit("unit.tick", thread=tid, i=i)
            else:
                with obs.span("unit.work", registry=reg, thread=tid):
                    pass

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    # every line parses; per-kind key sets are identical (schema-stable)
    events = obs.read_events(path)
    assert len(events) == n_threads * n_events
    keysets = {}
    for e in events:
        assert isinstance(e["ts"], float) and e["kind"]
        keysets.setdefault(e["kind"], set()).add(frozenset(e))
    assert all(len(variants) == 1 for variants in keysets.values()), keysets
    ticks = obs.read_events(path, kind="unit.tick")
    spans = obs.read_events(path, kind="span")
    assert len(ticks) == n_threads * (n_events // 2)
    assert len(spans) == n_threads * (n_events // 2)
    assert {e["name"] for e in spans} == {"unit.work"}


def test_emit_shields_broken_sinks(caplog):
    """A sink whose write raises (disk full, closed file) must not crash
    the emitting thread — the failure is logged once and later events
    keep flowing to healthy sinks."""
    reg = obs.MetricsRegistry()
    good = []

    class Boom:
        def write(self, e):
            raise OSError("disk full")

    class Good:
        def write(self, e):
            good.append(e)

    reg.add_event_sink(Boom())
    reg.add_event_sink(Good())
    with caplog.at_level("ERROR", "analytics_zoo_tpu.observability"):
        reg.emit("a")
        reg.emit("b")          # must not raise either
    assert [e["kind"] for e in good] == ["a", "b"]
    assert sum("event sink" in r.message for r in caplog.records) == 1


def test_span_nesting_records_parent_and_histogram():
    reg = obs.MetricsRegistry()
    events = []

    class ListSink:
        def write(self, e):
            events.append(e)

    reg.add_event_sink(ListSink())
    assert obs.current_span() is None
    with obs.span("outer", registry=reg):
        assert obs.current_span() == "outer"
        with obs.span("inner", registry=reg):
            assert obs.current_span() == "inner"
    assert obs.current_span() is None
    by_name = {e["name"]: e for e in events}
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["outer"]["parent"] is None
    snap = reg.snapshot()
    assert snap['zoo_span_seconds{span="inner"}']["count"] == 1
    assert snap['zoo_span_seconds{span="outer"}']["sum"] >= \
        snap['zoo_span_seconds{span="inner"}']["sum"]


def test_tensorboard_sink_roundtrip(tmp_path):
    from analytics_zoo_tpu.utils.tensorboard import read_scalars

    r = obs.MetricsRegistry()
    r.counter("zoo_served_total").inc(5)
    r.histogram("zoo_wait_seconds").observe(0.25, n=4)
    sink = obs.TensorBoardSink(str(tmp_path))
    sink.export(r, step=1)
    sink.close()
    pts = {tag: v for _, v, _, tag in read_scalars(str(tmp_path))}
    assert pts["zoo_served_total"] == 5
    assert pts["zoo_wait_seconds_count"] == 4
    assert pts["zoo_wait_seconds_mean"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# serving smoke: scrape and JSON log reconcile with ground truth (tier-1)
# ---------------------------------------------------------------------------


def _toy_model():
    from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    init_zoo_context()
    m = Sequential()
    m.add(Dense(4, input_shape=(6,), activation="relu"))
    m.add(Dense(3, activation="softmax"))
    m.init_weights()
    return m


def test_serving_smoke_counters_reconcile_exactly(tmp_path):
    """N requests through the real stack: the scraped exposition and the
    JSON event log must independently agree with ground truth — served
    counter == N, batch-size histogram sum == N, zero failure counters."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           LocalBackend, OutputQueue)

    n = 24
    reg = obs.MetricsRegistry()
    im = InferenceModel(registry=reg).from_keras(_toy_model())
    backend = LocalBackend()
    events_path = str(tmp_path / "serving_events.jsonl")
    serving = (ClusterServing(im, backend=backend, batch_size=8,
                              registry=reg)
               .set_json_events(events_path))
    scrape = serving.serve_metrics(port=0)
    serving.start()
    inq, outq = InputQueue(backend), OutputQueue(backend)
    rng = np.random.default_rng(0)
    for i in range(n):
        inq.enqueue(f"r-{i}", rng.normal(size=(6,)).astype(np.float32))
    for i in range(n):
        assert outq.query(f"r-{i}", timeout=30.0) is not None
    # scrape while running (the endpoint is live alongside the loop). The
    # loop publishes results BEFORE bumping counters, so poll briefly
    # until the final batch's increments land
    import time
    deadline = time.monotonic() + 10.0
    while True:
        with urllib.request.urlopen(scrape.url, timeout=10.0) as resp:
            assert resp.status == 200
            text = resp.read().decode("utf-8")
        parsed = obs.parse_prometheus(text)
        done = [v for name, _, v in
                parsed["zoo_serving_records_total"]["samples"]]
        if (done and done[0] >= n) or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    serving.stop()

    def value(family, suffix=""):
        name = family + suffix
        vals = [v for s_name, _, v in parsed[family]["samples"]
                if s_name == name]
        assert len(vals) == 1, (name, parsed[family]["samples"])
        return vals[0]

    assert value("zoo_serving_records_total") == n
    assert value("zoo_serving_batch_size", "_sum") == n
    assert value("zoo_serving_batch_size", "_count") == \
        value("zoo_serving_batches_total")
    assert value("zoo_serving_failures_total") == 0
    assert value("zoo_serving_undecodable_total") == 0
    assert value("zoo_serving_queue_wait_seconds", "_count") == n
    assert value("zoo_serving_dispatch_seconds", "_count") >= 1
    # inference-layer metrics flow through the same registry
    assert value("zoo_inference_records_total") >= n

    # the JSON event log independently reconciles
    flushes = obs.read_events(events_path, kind="serving.flush")
    assert sum(e["records"] for e in flushes) == n
    assert len(flushes) == value("zoo_serving_batches_total")
    assert not obs.read_events(events_path, kind="serving.failure")
    spans = obs.read_events(events_path, kind="span")
    assert {"serving.dispatch", "serving.flush"} <= \
        {e["name"] for e in spans}


def test_serving_per_request_traces_reconcile_exactly(tmp_path):
    """Tier-1 acceptance: every served record emits exactly four
    parent-linked request events (enqueue→dequeue→dispatch→publish)
    sharing ONE trace id; trace count == N with zero orphans; and the
    scrape exposes p50/p95/p99 quantile series with p99 >= p50 for
    queue-wait and dispatch."""
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           LocalBackend, OutputQueue)

    n = 24
    reg = obs.MetricsRegistry()
    im = InferenceModel(registry=reg).from_keras(_toy_model())
    backend = LocalBackend()
    events_path = str(tmp_path / "trace_events.jsonl")
    serving = (ClusterServing(im, backend=backend, batch_size=8,
                              registry=reg)
               .set_json_events(events_path))
    scrape = serving.serve_metrics(port=0)
    serving.start()
    inq, outq = InputQueue(backend), OutputQueue(backend)
    rng = np.random.default_rng(11)
    for i in range(n):
        inq.enqueue(f"t-{i}", rng.normal(size=(6,)).astype(np.float32))
    for i in range(n):
        assert outq.query(f"t-{i}", timeout=30.0) is not None
    # the final batch's publish events land just after its results do
    import time
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if len(obs.read_events(events_path, kind="request")) >= 4 * n:
            break
        time.sleep(0.05)
    with urllib.request.urlopen(scrape.url, timeout=10.0) as resp:
        text = resp.read().decode("utf-8")
    serving.stop()

    # ---- event-log reconciliation against ground truth ----
    events = obs.read_events(events_path, kind="request")
    assert len(events) == 4 * n, "phase-event count != 4 per record"
    by_trace = {}
    for e in events:
        assert set("0123456789abcdef") >= set(e["trace"]) and \
            len(e["trace"]) == 16, "trace id format (16 hex chars)"
        by_trace.setdefault(e["trace"], {})[e["phase"]] = e
    assert len(by_trace) == n, "one trace id per served record, no orphans"
    expected_parent = {"enqueue": None, "dequeue": "enqueue",
                       "dispatch": "dequeue", "publish": "dispatch"}
    uris = set()
    for trace, phases in by_trace.items():
        assert set(phases) == set(expected_parent), trace
        for phase, e in phases.items():
            assert e["parent"] == expected_parent[phase]
        # one uri per trace, consistent across all four phases
        assert len({e["uri"] for e in phases.values()}) == 1
        uris.add(phases["publish"]["uri"])
        assert phases["publish"]["e2e_s"] >= phases["publish"]["dur_s"] >= 0
        assert phases["dequeue"]["dur_s"] >= 0
    assert uris == {f"t-{i}" for i in range(n)}

    # ---- scrape-side quantiles ----
    parsed = obs.parse_prometheus(text)
    for fam in ("zoo_serving_queue_wait_quantiles_seconds",
                "zoo_serving_dispatch_quantiles_seconds",
                "zoo_serving_e2e_quantiles_seconds"):
        assert parsed[fam]["type"] == "summary", fam
        qs = {lab["quantile"]: v for name, lab, v in
              parsed[fam]["samples"] if name == fam}
        assert set(qs) == {"0.5", "0.95", "0.99"}, fam
        assert qs["0.5"] <= qs["0.95"] <= qs["0.99"], fam
        assert all(v == v and v >= 0 for v in qs.values()), fam
    count = next(v for name, _, v in
                 parsed["zoo_serving_queue_wait_quantiles_seconds"]["samples"]
                 if name.endswith("_count"))
    assert count == n


def test_serving_healthz_statusz_live(tmp_path):
    """/healthz reports ok (with running=True serve-loop state) while the
    loop runs; /statusz adds stream depth, last-flush age, jit totals,
    and device info; both flip to running=False after stop()."""
    import json as _json

    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           LocalBackend, OutputQueue)

    reg = obs.MetricsRegistry()
    im = InferenceModel(registry=reg).from_keras(_toy_model())
    backend = LocalBackend()
    serving = ClusterServing(im, backend=backend, batch_size=4, registry=reg)
    scrape = serving.serve_metrics(port=0)
    base = f"http://{scrape.host}:{scrape.port}"
    serving.start()
    try:
        inq, outq = InputQueue(backend), OutputQueue(backend)
        inq.enqueue("h-0", np.zeros(6, np.float32))
        assert outq.query("h-0", timeout=30.0) is not None
        with urllib.request.urlopen(base + "/healthz", timeout=10.0) as r:
            health = _json.loads(r.read())
        assert health["status"] == "ok"
        assert health["uptime_s"] >= 0
        assert health["serving"]["running"] is True
        with urllib.request.urlopen(base + "/statusz", timeout=10.0) as r:
            status = _json.loads(r.read())
        assert status["serving"]["stream_depth"] == 0
        assert status["serving"]["served"] == 1
        assert status["serving"]["last_flush_age_s"] >= 0
        assert status["jit"]["compile_total"] >= 1   # the predict compile
        assert status["device"]["platform"] == "cpu"
        assert status["device"]["device_count"] >= 1
    finally:
        # read running=False through a still-open endpoint: close the
        # scrape AFTER stop() (stop() would close it, so detach first)
        serving._scrape = None
        serving.stop(drain=False)
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=10.0) as r:
            health = _json.loads(r.read())
        assert health["serving"]["running"] is False
    finally:
        scrape.close()


def test_scrape_server_concurrent_scrape_while_serving():
    """Scrape-while-observe torture: producer threads hammer a histogram,
    a summary, and a counter while scrapes run — every exposition parses
    cleanly (no torn output) and histogram bucket monotonicity + the
    +Inf==count invariant hold mid-flight."""
    reg = obs.MetricsRegistry()
    h = reg.histogram("zoo_load_seconds", "under fire")
    s = reg.summary("zoo_load_quantiles_seconds", "under fire")
    c = reg.counter("zoo_load_total")
    srv = obs.ScrapeServer(reg, port=0)
    stop = threading.Event()

    def producer(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            v = float(rng.random())
            h.observe(v)
            s.observe(v)
            c.inc()

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(20):
            with urllib.request.urlopen(srv.url, timeout=10.0) as resp:
                text = resp.read().decode("utf-8")
            parsed = obs.parse_prometheus(text)   # raises on torn lines
            samples = parsed["zoo_load_seconds"]["samples"]
            buckets = [v for name, lab, v in samples
                       if name.endswith("_bucket")]
            assert buckets == sorted(buckets), "bucket monotonicity"
            count = next(v for name, _, v in samples
                         if name.endswith("_count"))
            assert buckets[-1] == count, "+Inf bucket == count"
            qs = {lab["quantile"]: v for name, lab, v in
                  parsed["zoo_load_quantiles_seconds"]["samples"]
                  if "quantile" in lab}
            if qs and all(v == v for v in qs.values()):
                assert qs["0.5"] <= qs["0.95"] <= qs["0.99"]
    finally:
        stop.set()
        for t in threads:
            t.join()
        srv.close()


def test_serving_error_paths_counted(tmp_path):
    """Undecodable payloads and inference failures land in their counters
    and the event log — not just in text logs."""
    from analytics_zoo_tpu.serving import (ClusterServing, InputQueue,
                                           LocalBackend, OutputQueue,
                                           ServingError)
    from analytics_zoo_tpu.serving.client import INPUT_STREAM

    class BoomModel:
        def predict(self, x):
            raise RuntimeError("boom")

    reg = obs.MetricsRegistry()
    backend = LocalBackend()
    events_path = str(tmp_path / "errors.jsonl")
    serving = (ClusterServing(BoomModel(), backend=backend, batch_size=2,
                              registry=reg)
               .set_json_events(events_path).start())
    backend.xadd(INPUT_STREAM, {"uri": "bad", "data": "!!notb64!!"})
    inq, outq = InputQueue(backend), OutputQueue(backend)
    inq.enqueue("x1", np.zeros(3, np.float32))
    with pytest.raises(ServingError):
        outq.query("x1", timeout=10.0)
    with pytest.raises(ServingError):
        outq.query("bad", timeout=10.0)
    serving.stop()
    snap = reg.snapshot()
    assert snap["zoo_serving_undecodable_total"]["value"] == 1
    assert snap["zoo_serving_failures_total"]["value"] == 1
    assert snap["zoo_serving_records_total"]["value"] == 0
    assert len(obs.read_events(events_path, kind="serving.undecodable")) == 1
    assert sum(e["records"] for e in
               obs.read_events(events_path, kind="serving.failure")) == 1
    # the failed record's trace chain terminates in a `failed` phase —
    # it must not read as forever in-flight
    reqs = obs.read_events(events_path, kind="request")
    x1 = [e for e in reqs if e["uri"] == "x1"]
    phases = {e["phase"] for e in x1}
    assert "failed" in phases and "publish" not in phases
    assert len({e["trace"] for e in x1}) == 1


def test_scrape_server_404_on_unknown_path():
    reg = obs.MetricsRegistry()
    reg.counter("zoo_x_total").inc()
    srv = obs.ScrapeServer(reg, port=0)
    try:
        with urllib.request.urlopen(srv.url, timeout=10.0) as resp:
            assert "zoo_x_total 1" in resp.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/nope", timeout=10.0)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# fit instrumentation (tier-1 acceptance: nonzero step-time histogram and
# throughput gauge, without changing training results)
# ---------------------------------------------------------------------------


def _xor_fit(nb_epoch=3):
    import optax

    from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense

    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]] * 8, np.float32)
    y = (x[:, 0].astype(np.int32) ^ x[:, 1].astype(np.int32))
    m = Sequential()
    m.add(Dense(8, input_shape=(2,), activation="relu"))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer=optax.adam(1e-2), loss="scce")
    history = m.fit(x, y, batch_size=8, nb_epoch=nb_epoch)
    return m, history


def test_fit_reports_step_time_and_throughput():
    obs.reset_default_registry()
    init_zoo_context()
    _, history = _xor_fit(nb_epoch=3)
    snap = obs.default_registry().snapshot()
    h = snap["zoo_train_step_seconds"]
    assert h["count"] == 3 * 4          # 3 epochs x 4 steps of 8/32
    assert h["sum"] > 0
    assert snap["zoo_train_records_per_sec"]["value"] > 0
    assert snap["zoo_train_steps_total"]["value"] == 12
    assert snap["zoo_train_examples_total"]["value"] == 3 * 32
    assert len(history["loss"]) == 3
    assert snap['zoo_span_seconds{span="train.fit"}']["count"] == 1


def test_fit_mfu_gauge_with_known_peak(monkeypatch):
    """The achieved-MFU plumbing: with ``zoo.metrics.flops`` on and a chip
    peak known (monkeypatched — the CPU test mesh publishes none), fit
    sets a plausible nonzero MFU gauge from XLA cost analysis."""
    from analytics_zoo_tpu.utils import profiling

    obs.reset_default_registry()
    init_zoo_context(metrics_flops=True)
    monkeypatch.setattr(profiling, "device_peak_flops",
                        lambda device=None: 1e12)
    _xor_fit(nb_epoch=2)
    snap = obs.default_registry().snapshot()
    assert 0 < snap["zoo_train_mfu"]["value"] < 1


def test_device_peak_flops_none_off_tpu_raises_on_unknown_tpu_kind():
    """Off-TPU there is no published peak (None, and ``mfu`` stays None);
    a TPU kind nobody entered is an error, not a silently missing MFU."""
    import types

    import jax
    import pytest

    from analytics_zoo_tpu.utils import profiling

    assert profiling.device_peak_flops(jax.devices()[0]) is None
    assert profiling.mfu(1e12) is None
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert profiling.device_peak_flops(v5e) == 197e12
    # substring guesses are gone: the key is the exact reported string
    for kind in ("TPU v9 mega", "TPU v5 lite pod", "tpu v5 lite"):
        with pytest.raises(KeyError, match="no published peak"):
            profiling.device_peak_flops(
                types.SimpleNamespace(platform="tpu", device_kind=kind))


def test_fit_mfu_flag_enabled_after_first_fit(monkeypatch):
    """The flops flag is re-read per dispatch — a first fit with it off
    must not latch MFU off for later fits on the same compiled model."""
    import optax

    from analytics_zoo_tpu.pipeline.api.keras.engine import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.utils import profiling

    obs.reset_default_registry()
    init_zoo_context()                       # flag off
    monkeypatch.setattr(profiling, "device_peak_flops",
                        lambda device=None: 1e12)
    x = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    m = Sequential()
    m.add(Dense(4, input_shape=(4,), activation="relu"))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer=optax.adam(1e-2), loss="scce")
    m.fit(x, y, batch_size=16, nb_epoch=1)
    assert obs.default_registry().snapshot()["zoo_train_mfu"]["value"] == 0
    init_zoo_context(metrics_flops=True)     # enable AFTER the first fit
    m.fit(x, y, batch_size=16, nb_epoch=1)
    assert obs.default_registry().snapshot()["zoo_train_mfu"]["value"] > 0


def test_fit_metrics_off_by_default_do_not_compute_flops():
    """Without the opt-in flag the MFU gauge stays unset (no cost-analysis
    compile is spent) while the step-time histogram still fills."""
    obs.reset_default_registry()
    init_zoo_context()
    _xor_fit(nb_epoch=1)
    snap = obs.default_registry().snapshot()
    assert snap["zoo_train_mfu"]["value"] == 0
    assert snap["zoo_train_step_seconds"]["count"] > 0


def test_fit_counts_jit_compiles_and_forced_retrace_emits_one_event():
    """Tier-1 acceptance: after one fit, zoo_jit_compile_total is nonzero;
    a forced re-trace (changed input batch shape) emits exactly ONE
    jit.retrace event (for train.step) and bumps the labeled retrace
    counter."""
    obs.reset_default_registry()
    init_zoo_context()
    events = []

    class ListSink:
        def write(self, e):
            events.append(e)

    obs.default_registry().add_event_sink(ListSink())
    m, _ = _xor_fit(nb_epoch=1)               # batch_size=8 inside
    snap = obs.default_registry().snapshot()
    assert snap["zoo_jit_compile_total"]["value"] >= 1
    assert snap['zoo_jit_compile_seconds{fn="train.step"}']["count"] == 1
    assert not [e for e in events if e["kind"] == "jit.retrace"]
    compiles_before = [e for e in events if e["kind"] == "jit.compile"]
    assert compiles_before, "first compile must emit a jit.compile event"

    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]] * 8, np.float32)
    y = (x[:, 0].astype(np.int32) ^ x[:, 1].astype(np.int32))
    m.fit(x, y, batch_size=16, nb_epoch=1)    # new shape → exactly 1 retrace
    retraces = [e for e in events if e["kind"] == "jit.retrace"]
    assert len(retraces) == 1
    assert retraces[0]["fn"] == "train.step"
    assert retraces[0]["n_signatures"] == 2
    snap = obs.default_registry().snapshot()
    assert snap['zoo_jit_retrace_total{fn="train.step"}']["value"] == 1
    # a third fit on an ALREADY-SEEN shape must not count again
    m.fit(x, y, batch_size=16, nb_epoch=1)
    assert len([e for e in events if e["kind"] == "jit.retrace"]) == 1


def test_evaluate_and_predict_report_step_time_and_records():
    """The ROADMAP eval/predict instrumentation pass: both paths fill
    their weighted step-time histograms, record counters, and spans —
    mirroring what fit got in PR 2."""
    obs.reset_default_registry()
    init_zoo_context()
    m, _ = _xor_fit(nb_epoch=1)
    x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]] * 8, np.float32)
    y = (x[:, 0].astype(np.int32) ^ x[:, 1].astype(np.int32))
    m.evaluate(x, y, batch_size=8)
    preds = m.predict(x, batch_size=8)
    assert preds.shape == (32, 2)
    snap = obs.default_registry().snapshot()
    assert snap["zoo_eval_step_seconds"]["count"] == 4     # 32/8 batches
    assert snap["zoo_eval_step_seconds"]["sum"] > 0
    assert snap["zoo_eval_examples_total"]["value"] == 32  # pads excluded
    assert snap["zoo_predict_step_seconds"]["count"] == 4
    assert snap["zoo_predict_examples_total"]["value"] == 32
    assert snap['zoo_span_seconds{span="train.evaluate"}']["count"] == 1
    assert snap['zoo_span_seconds{span="train.predict"}']["count"] == 1
    # eval/predict compiles are visible to the compile counter too
    assert snap['zoo_jit_compile_seconds{fn="train.eval_step"}']["count"] == 1
    assert snap['zoo_jit_compile_seconds{fn="train.predict_step"}']["count"] \
        == 1


def test_bench_snapshot_shape():
    """The compact snapshot bench.py embeds per round: flat keys, no
    bucket arrays, JSON-serializable."""
    r = _populated_registry()
    compact = r.snapshot(compact=True)
    js = json.loads(json.dumps(compact))
    for key, entry in js.items():
        assert entry["type"] in ("counter", "gauge", "histogram")
        if entry["type"] == "histogram":
            assert "buckets" not in entry
            assert set(entry) == {"type", "count", "sum", "mean"}
