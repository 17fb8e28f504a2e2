"""Observability layer unit tests: tracer determinism under a fake clock,
Chrome trace-event schema validation, metrics registry semantics
(get-or-create, type/bucket conflicts, Prometheus exposition), NaN-free
snapshots at zero completions, and histogram property tests (bucket-count
conservation, quantile bounds, merge associativity) under hypothesis — or
the `tests/_hypothesis_fallback` harness on machines without it."""
import json

import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.obs import (
    LATENCY_BUCKETS_S,
    MetricsRegistry,
    Tracer,
    render_report,
    summarize_trace,
    validate_chrome_trace,
)
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.obs.summary import async_durations, span_groups


class FakeClock:
    def __init__(self, t0: float = 0.0, step: float = 0.0):
        self.t = t0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def _record_session(tracer):
    tracer.name_track(OT.TID_ENGINE, "engine")
    tracer.complete("form_batch", 1.0, 1.5, cat="pipeline",
                    tid=OT.TID_SCHED, args={"bucket": 4})
    tracer.instant("retrace:Body", 2.0, cat="retrace")
    tracer.counter("queue_depth", {"pending": 3}, 2.5)
    tracer.async_begin("request", 7, 3.0, cat="request:m")
    tracer.async_end("request", 7, 4.0, cat="request:m",
                     args={"status": "ok"})
    with tracer.span("tune:dw", cat="tune", tid=OT.TID_TUNE):
        pass


def test_tracer_deterministic_under_fake_clock():
    """Two identically-driven fake-clock tracers export byte-identical
    JSON — the trace of a deterministic run is itself deterministic."""
    docs = []
    for _ in range(2):
        tracer = Tracer(FakeClock(step=0.125), origin_s=0.0)
        _record_session(tracer)
        docs.append(json.dumps(tracer.to_chrome(), sort_keys=True))
    assert docs[0] == docs[1]


def _exported(tracer):
    return [ev for ev in tracer.to_chrome()["traceEvents"] if ev["ph"] != "M"]


def test_tracer_timebase_microseconds_from_origin():
    tracer = Tracer(FakeClock(), origin_s=10.0)
    tracer.complete("work", 10.5, 10.75)
    (ev,) = _exported(tracer)
    assert ev["ts"] == pytest.approx(0.5e6)
    assert ev["dur"] == pytest.approx(0.25e6)
    # inverted span (clock skew between explicit stamps) clamps, not negates
    tracer.complete("skew", 11.0, 10.0)
    assert _exported(tracer)[-1]["dur"] == 0.0


def test_tracer_export_keeps_chrome_shape():
    """Events stored as tuples export as the same Chrome dicts, key order
    included: fake-clock documents stay byte-for-byte what they were."""
    tracer = Tracer(FakeClock(step=0.125), origin_s=0.0)
    _record_session(tracer)
    got = json.dumps(_exported(tracer))
    want = json.dumps([
        {"ph": "X", "name": "form_batch", "cat": "pipeline", "pid": 0,
         "tid": OT.TID_SCHED, "ts": 1e6, "dur": 0.5e6,
         "args": {"bucket": 4}},
        {"ph": "i", "name": "retrace:Body", "cat": "retrace", "pid": 0,
         "tid": OT.TID_ENGINE, "ts": 2e6, "s": "t"},
        {"ph": "C", "name": "queue_depth", "pid": 0, "tid": OT.TID_ENGINE,
         "ts": 2.5e6, "args": {"pending": 3}},
        {"ph": "b", "name": "request", "cat": "request:m", "id": 7,
         "pid": 0, "tid": OT.TID_REQUESTS, "ts": 3e6},
        {"ph": "e", "name": "request", "cat": "request:m", "id": 7,
         "pid": 0, "tid": OT.TID_REQUESTS, "ts": 4e6,
         "args": {"status": "ok"}},
        {"ph": "X", "name": "tune:dw", "cat": "tune", "pid": 0,
         "tid": OT.TID_TUNE, "ts": 0.125e6, "dur": 0.125e6},
    ])
    assert got == want
    assert "otherData" not in tracer.to_chrome()


def test_tracer_events_untracked_after_collection():
    """Stored events hold no container the collector tracks: after one
    collection none of them is walked again, however long the run."""
    import gc

    tracer = Tracer(FakeClock(step=1e-3), origin_s=0.0)
    for b in range(50):
        tracer.complete("form_batch", 1.0, 1.5, cat="pipeline",
                        tid=OT.TID_SCHED,
                        args={"bucket": 8, "batch": b,
                              "rids": tuple(range(8 * b, 8 * b + 8))})
        tracer.complete("dispatch:head", 1.5, 1.6, cat="stage",
                        args={"rows": 8, "batch": b})
        tracer.async_begin("request", b, 1.0, cat="request:m",
                           args={"model": "m", "deadline_s": None})
        tracer.async_end("request", b, 2.0, cat="request:m",
                         args={"status": "ok"})
        tracer.instant("router_dispatch", cat="router")
    assert len(tracer.events) == 250
    gc.collect()
    # flat events go at the first collection; a form_batch event holds
    # its request-id tuple, which goes first, and the event at the next
    tracked = [ev[1] for ev in tracer.events if gc.is_tracked(ev)]
    assert set(tracked) <= {"form_batch"}
    gc.collect()
    assert not any(gc.is_tracked(ev) for ev in tracer.events)


def test_tracer_exports_wall_clock_origin():
    """A default-clock tracer notes its origin on the wall clock, so its
    spans can be laid on a profiler session's `time.time_ns` clock; a
    fake clock has no wall-clock meaning and exports none."""
    import time

    before = time.time_ns()
    tracer = Tracer()
    after = time.time_ns()
    origin = tracer.to_chrome()["otherData"]["origin_unix_ns"]
    assert isinstance(origin, int)
    assert before - 5_000_000 <= origin <= after + 5_000_000
    # an explicit origin on the default clock shifts the wall origin too
    t = time.perf_counter()
    shifted = Tracer(origin_s=t - 2.0).origin_unix_ns
    assert abs(shifted - (time.time_ns() - 2_000_000_000)) < 5_000_000
    assert "otherData" not in Tracer(FakeClock(), origin_s=0.0).to_chrome()


def test_tracer_export_and_validate():
    tracer = Tracer(FakeClock(step=0.1), origin_s=0.0,
                    process_name="test-proc")
    _record_session(tracer)
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    # metadata first: process name + every named track precede the events
    metas = [ev for ev in doc["traceEvents"] if ev["ph"] == "M"]
    assert metas and doc["traceEvents"][:len(metas)] == metas
    names = {ev["args"]["name"] for ev in metas}
    assert {"test-proc", "engine"} <= names


def test_tracer_name_track_dedupes():
    tracer = Tracer(FakeClock(), origin_s=0.0)
    tracer.name_track(5, "stage:Body")
    tracer.name_track(5, "stage:Body")
    thread_metas = [ev for ev in tracer.to_chrome()["traceEvents"]
                    if ev["ph"] == "M" and ev["name"] == "thread_name"]
    assert len(thread_metas) == 1


def test_tracer_save_roundtrip(tmp_path):
    tracer = Tracer(FakeClock(step=0.1), origin_s=0.0)
    _record_session(tracer)
    path = tracer.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        loaded = json.load(f)
    assert validate_chrome_trace(loaded) == []
    assert loaded == json.loads(json.dumps(tracer.to_chrome()))


@pytest.mark.parametrize("doc, fragment", [
    ([], "traceEvents"),
    ({"traceEvents": 5}, "not an array"),
    ({"traceEvents": [{"ph": "Z", "name": "x", "pid": 0, "tid": 0}]},
     "unknown phase"),
    ({"traceEvents": [{"ph": "i", "pid": 0, "tid": 0, "ts": 1, "s": "t"}]},
     "missing name"),
    ({"traceEvents": [{"ph": "i", "name": "x", "ts": 1}]}, "integer"),
    ({"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                       "ts": 1, "dur": -2.0}]}, "dur"),
    ({"traceEvents": [{"ph": "C", "name": "x", "pid": 0, "tid": 0,
                       "ts": 1}]}, "args"),
    ({"traceEvents": [{"ph": "e", "name": "r", "cat": "request", "id": 1,
                       "pid": 0, "tid": 0, "ts": 1}]}, "without begin"),
    ({"traceEvents": [{"ph": "b", "name": "r", "cat": "request", "id": 1,
                       "pid": 0, "tid": 0, "ts": 1}]}, "without end"),
    ({"traceEvents": [{"ph": "b", "name": "r", "pid": 0, "tid": 0,
                       "ts": 1, "id": 1}]}, "id and cat"),
])
def test_validate_catches_schema_violations(doc, fragment):
    errors = validate_chrome_trace(doc)
    assert errors and any(fragment in e for e in errors), errors


def test_null_tracer_is_falsy_noop():
    assert not OT.NULL
    OT.NULL.complete("x", 0.0, 1.0)
    OT.NULL.instant("x")
    with OT.NULL.span("x"):
        pass
    assert OT.NULL.to_chrome() == {"traceEvents": []}
    with pytest.raises(ValueError):
        OT.NULL.save("/tmp/never.json")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_monotone():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", labels={"model": "m"})
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("x_total", labels={"model": "a"})
    assert reg.counter("x_total", labels={"model": "a"}) is a
    # same name, different labels: a sibling, not the same handle
    assert reg.counter("x_total", labels={"model": "b"}) is not a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("lat_seconds", buckets=(0.5, 1.0))


def test_histogram_rejects_bad_bounds():
    with pytest.raises(ValueError, match="at least one"):
        OM.Histogram("h", ())
    with pytest.raises(ValueError, match="strictly"):
        OM.Histogram("h", (1.0, 1.0, 2.0))
    with pytest.raises(ValueError, match="strictly"):
        OM.Histogram("h", (2.0, 1.0))
    with pytest.raises(ValueError, match="finite"):
        OM.Histogram("h", (1.0, float("inf")))


def test_snapshot_safe_at_zero_completions():
    """A snapshot before any traffic has no NaN anywhere — every value is
    finite-or-None, so strict JSON encoding succeeds."""
    reg = MetricsRegistry()
    reg.counter("reqs_total")
    g = reg.gauge("fps")
    reg.histogram("lat_seconds")
    g.set(float("nan"))  # a gauge fed garbage must not poison the export
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["gauges"]["fps"] is None
    h = snap["histograms"]["lat_seconds"]
    assert h["count"] == 0
    assert h["p50"] is None and h["p95"] is None and h["p99"] is None


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests served",
                labels={"model": "m"}).inc(3)
    reg.gauge("fps").set(42.0)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{model="m"} 3.0' in text
    assert "# HELP reqs_total requests served" in text
    assert "# TYPE lat_seconds histogram" in text
    # cumulative le rows; the +Inf bucket equals the total count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1.0"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text


def test_registry_save_formats(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    prom = tmp_path / "m.prom"
    reg.save(str(prom))
    assert "# TYPE x_total counter" in prom.read_text()
    js = tmp_path / "m.json"
    reg.save(str(js))
    assert json.loads(js.read_text())["counters"]["x_total"] == 1.0


def test_null_registry_is_falsy_and_absorbs():
    assert not OM.NULL_REGISTRY
    c = OM.NULL_REGISTRY.counter("anything")
    assert c is OM.NULL_INSTRUMENT
    c.inc()
    c.observe(1.0)
    c.set(2.0)
    c.dec()


# ---------------------------------------------------------------------------
# histogram properties
# ---------------------------------------------------------------------------


@settings(max_examples=30)
@given(n=st.integers(min_value=0, max_value=64),
       seed=st.integers(min_value=0, max_value=10_000))
def test_histogram_count_conservation(n, seed):
    """Every observation lands in exactly one bucket: sum(counts) == count
    and sum == the running total, for arbitrary value streams."""
    import random
    rng = random.Random(seed)
    h = OM.Histogram("h", LATENCY_BUCKETS_S)
    total = 0.0
    for _ in range(n):
        v = rng.uniform(0.0, 20.0)
        h.observe(v)
        total += v
    assert sum(h.counts) == h.count == n
    assert h.sum == pytest.approx(total)


@settings(max_examples=30)
@given(n=st.integers(min_value=1, max_value=64),
       q=st.floats(min_value=0.0, max_value=1.0),
       seed=st.integers(min_value=0, max_value=10_000))
def test_histogram_quantile_bounded_by_bucket_range(n, q, seed):
    import random
    rng = random.Random(seed)
    h = OM.Histogram("h", LATENCY_BUCKETS_S)
    for _ in range(n):
        h.observe(rng.uniform(0.0, 20.0))
    est = h.quantile(q)
    assert est is not None
    assert 0.0 <= est <= LATENCY_BUCKETS_S[-1]


@settings(max_examples=20)
@given(na=st.integers(min_value=0, max_value=32),
       nb=st.integers(min_value=0, max_value=32),
       nc=st.integers(min_value=0, max_value=32),
       seed=st.integers(min_value=0, max_value=10_000))
def test_histogram_merge_associative_commutative(na, nb, nc, seed):
    """merge is a pointwise sum under identical bounds: (a+b)+c == a+(b+c)
    and a+b == b+a — shard-local histograms compose into the fleet view
    in any order."""
    import random
    rng = random.Random(seed)

    def make(n):
        h = OM.Histogram("h", LATENCY_BUCKETS_S)
        for _ in range(n):
            h.observe(rng.uniform(0.0, 20.0))
        return h

    a, b, c = make(na), make(nb), make(nc)

    def state(h):
        return (h.counts, h.count, pytest.approx(h.sum))

    assert state(a.merge(b).merge(c)) == state(a.merge(b.merge(c)))
    assert state(a.merge(b)) == state(b.merge(a))


def test_histogram_merge_requires_identical_buckets():
    a = OM.Histogram("h", (0.1, 1.0))
    b = OM.Histogram("h", (0.2, 1.0))
    with pytest.raises(ValueError, match="different buckets"):
        a.merge(b)


# ---------------------------------------------------------------------------
# summary
# ---------------------------------------------------------------------------


def _evt(ph, name, ts, **kw):
    return dict({"ph": ph, "name": name, "pid": 0, "tid": 0, "ts": ts}, **kw)


def test_async_durations_matches_cat_prefix_and_keys_by_cat_id():
    """The engine qualifies the request category per model; rids are only
    unique per model, so pairing must key on (cat, id) — two models' rid=0
    on one shared tracer must not collide."""
    events = [
        _evt("b", "request", 0.0, cat="request:a", id=0),
        _evt("b", "request", 0.0, cat="request:b", id=0),
        _evt("e", "request", 2e6, cat="request:a", id=0),
        _evt("e", "request", 5e6, cat="request:b", id=0),
        # unrelated category: ignored despite the name
        _evt("b", "request", 0.0, cat="other", id=0),
        _evt("e", "request", 9e6, cat="other", id=0),
    ]
    durs = async_durations(events, "request")
    assert durs == {("request:a", 0): pytest.approx(2.0),
                    ("request:b", 0): pytest.approx(5.0)}
    # exact (unqualified) category still matches
    exact = async_durations(
        [_evt("b", "request", 0.0, cat="request", id=3),
         _evt("e", "request", 1e6, cat="request", id=3)], "request")
    assert exact == {("request", 3): pytest.approx(1.0)}


def test_span_groups_sorted_by_total():
    events = [
        _evt("X", "small", 0.0, dur=10.0),
        _evt("X", "big", 0.0, dur=100.0),
        _evt("X", "small", 0.0, dur=20.0),
        _evt("i", "not_a_span", 0.0, s="t"),
    ]
    groups = span_groups(events)
    assert [g["name"] for g in groups] == ["big", "small"]
    small = groups[1]
    assert small["count"] == 2
    assert small["mean_us"] == pytest.approx(15.0)
    assert small["max_us"] == pytest.approx(20.0)


def test_summarize_and_render_zero_completions():
    """An empty trace + a zero-traffic snapshot render without NaN or
    division by zero — the mid-drain / nothing-served report is
    well-defined."""
    summary = summarize_trace({"traceEvents": []})
    assert summary["requests"]["completed"] == 0
    assert summary["requests"]["latency_p50_s"] is None
    assert summary["queue_wait"]["n"] == 0
    reg = MetricsRegistry()
    reg.histogram("lat_seconds")
    text = render_report(summary, reg.snapshot())
    assert "0 completed" in text
    assert "nan" not in text.lower()


def test_summarize_trace_counts_statuses():
    tracer = Tracer(FakeClock(step=0.5), origin_s=0.0)
    tracer.async_begin("request", 0, 1.0, cat="request:m")
    tracer.async_end("request", 0, 2.0, cat="request:m",
                     args={"status": "ok"})
    tracer.async_begin("request", 1, 1.0, cat="request:m")
    tracer.async_end("request", 1, 1.5, cat="request:m",
                     args={"status": "expired"})
    summary = summarize_trace(tracer.to_chrome())
    assert summary["requests"]["completed"] == 2
    assert summary["requests"]["by_status"] == {"ok": 1, "expired": 1}
    assert summary["requests"]["latency_p50_s"] == pytest.approx(0.5)
    assert summary["requests"]["latency_p99_s"] == pytest.approx(1.0)
