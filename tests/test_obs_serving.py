"""Observability wired through the serving, tuning, and training layers:
deterministic fake-clock traces of a pipelined drain, full request-lifecycle
coverage in the exported Chrome trace, obs-on bit-exactness, retrace-leak
detection (warning + metric + stats), NaN-free snapshots at zero
completions / all-expired drains, multi-model tracing on one shared
timeline, autotune provenance spans, and trainer metrics."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import jax

from repro.models import efficientnet as effn, mobilenet_v2 as mnv2
from repro.models.layers import make_calibrated_qnet
from repro.obs import (
    MetricsRegistry,
    Tracer,
    summarize_trace,
    validate_chrome_trace,
)
from repro.serve.vision import MultiModelEngine, VisionEngine
from repro.train import vision as V
from repro.tune import tune_qnet

HW = 32


class FakeClock:
    def __init__(self, t0: float = 0.0, step: float = 0.0):
        self.t = t0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def mnv2_qnet():
    return make_calibrated_qnet(
        mnv2.build(alpha=0.35, input_hw=HW, num_classes=10))


@pytest.fixture(scope="module")
def effnet_qnet():
    return make_calibrated_qnet(
        effn.build_compact(input_hw=HW, num_classes=10))


def _images(n, seed=7):
    return np.asarray(jax.random.uniform(
        jax.random.PRNGKey(seed), (n, HW, HW, 3), minval=-1, maxval=1))


def _traced_drain(qnet, n=4):
    """One full obs-enabled drain under a fake clock; returns
    (trace document, metrics registry, results)."""
    clock = FakeClock(step=1e-3)
    tracer = Tracer(clock, origin_s=0.0)
    reg = MetricsRegistry()
    eng = VisionEngine(qnet, buckets=(2,), clock=clock, tracer=tracer,
                       metrics=reg, name="m")
    rids = [eng.submit(img) for img in _images(n)]
    results = eng.run()
    assert sorted(results) == rids
    return tracer.to_chrome(), reg, results


# ---------------------------------------------------------------------------
# deterministic, schema-valid, lifecycle-complete traces
# ---------------------------------------------------------------------------


def test_trace_deterministic_across_runs(mnv2_qnet):
    """Fresh fake clock + fresh tracer, same inputs -> byte-identical
    exported trace: the obs layer adds no hidden nondeterminism."""
    doc1, _, _ = _traced_drain(mnv2_qnet)
    doc2, _, _ = _traced_drain(mnv2_qnet)
    assert json.dumps(doc1, sort_keys=True) == json.dumps(doc2,
                                                          sort_keys=True)


def test_trace_covers_every_request_lifecycle(mnv2_qnet):
    doc, reg, results = _traced_drain(mnv2_qnet, n=4)
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]

    def named(ph, name):
        return [ev for ev in events
                if ev["ph"] == ph and ev["name"] == name]

    # one open + one ok-close per admitted request, in the model's category
    begins = named("b", "request")
    ends = named("e", "request")
    assert {ev["id"] for ev in begins} == set(results)
    assert {ev["id"] for ev in ends} == set(results)
    assert all(ev["cat"] == "request:m" for ev in begins + ends)
    assert all(ev["args"]["status"] == "ok" for ev in ends)
    # queue-wait pairs for every request that rode a micro-batch
    assert len(named("b", "queue_wait")) == len(results)
    # 4 requests at bucket 2 -> 2 form_batch spans, one dispatch of the
    # whole-network program per micro-batch, one drain span over the run()
    form = named("X", "form_batch")
    assert len(form) == 2
    assert all(ev["args"]["bucket"] == 2 for ev in form)
    dispatches = [ev for ev in events
                  if ev["ph"] == "X" and ev["name"].startswith("dispatch:")]
    assert [ev["name"] for ev in dispatches] == ["dispatch:net"] * 2
    assert [ev["args"]["batch"] for ev in dispatches] == [0, 1]
    assert len(named("X", "drain")) == 1
    # the summary reconstructs the same lifecycle from the document alone
    summary = summarize_trace(doc)
    assert summary["requests"]["completed"] == len(results)
    assert summary["requests"]["by_status"] == {"ok": len(results)}
    assert summary["queue_wait"]["n"] == len(results)
    # metrics agree with the trace
    snap = reg.snapshot()
    assert snap["counters"]['serve_requests_completed_total{model="m"}'] == 4
    assert snap["counters"]['serve_micro_batches_total{model="m"}'] == 2
    assert snap["histograms"][
        'serve_request_latency_seconds{model="m"}']["count"] == 4
    json.dumps(snap, allow_nan=False)


def test_per_batch_spans_share_the_batch_id(mnv2_qnet):
    """A micro-batch's host life reads form_batch -> place -> dispatch:net
    -> harvest -> record, every span carrying the batch's id; only
    form_batch lists the request ids."""
    doc, _, results = _traced_drain(mnv2_qnet, n=6)
    spans = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    form = [ev for ev in spans if ev["name"] == "form_batch"]
    ids = [ev["args"]["batch"] for ev in form]
    assert ids == [0, 1, 2]
    assert sorted(r for ev in form for r in ev["args"]["rids"]) == sorted(results)
    for b in ids:
        mine = [ev for ev in spans if ev.get("args", {}).get("batch") == b]
        names = [ev["name"] for ev in mine]
        assert names.count("form_batch") == 1
        assert names.count("place") == 1 and names.count("record") == 1
        assert names.count("harvest") == 1
        assert sum(n.startswith("dispatch:") for n in names) == 1
        assert names.count("dispatch:net") == 1
        order = sorted(mine, key=lambda ev: ev["ts"])
        assert [ev["name"] for ev in order][:2] == ["form_batch", "place"]
        assert [ev["name"] for ev in order][-2:] == ["harvest", "record"]
        assert all("rids" not in ev["args"] for ev in mine
                   if ev["name"] != "form_batch")
        assert all(ev["args"]["rows"] == 2 for ev in mine
                   if ev["name"] in ("place", "record"))
    for name in ("place", "harvest", "record"):
        assert len([ev for ev in spans if ev["name"] == name]) == len(ids)
    # no per-submit counter events any more
    assert not [ev for ev in doc["traceEvents"] if ev["ph"] == "C"]


class CountingClock(FakeClock):
    def __init__(self):
        super().__init__(step=1e-3)
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return super().__call__()


def test_tracing_off_reads_the_clock_as_before(mnv2_qnet):
    """With tracing off, a drain reads the clock once per submit, twice
    around the drain, and per micro-batch once to form it, once when it is
    recorded and twice around the harvest: the spans cost nothing off."""
    clock = CountingClock()
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock, name="m")
    for img in _images(6):
        eng.submit(img)
    eng.run()
    assert clock.reads == 6 + 2 + 3 * 4
    clock.reads = 0
    mm = MultiModelEngine({"m": eng})
    for img in _images(4):
        mm.submit("m", img)
    mm.run()
    assert clock.reads == 4 + 2 + 2 * 4


def test_obs_on_is_bit_exact(mnv2_qnet):
    imgs = _images(4)
    plain = VisionEngine(mnv2_qnet, buckets=(2,))
    rids = [plain.submit(img) for img in imgs]
    want = plain.run()
    _, _, got = _traced_drain(mnv2_qnet, n=4)
    for rid in rids:
        np.testing.assert_array_equal(got[rid].logits, want[rid].logits)


# ---------------------------------------------------------------------------
# retrace-leak detection
# ---------------------------------------------------------------------------


def test_retrace_leak_warns_and_counts(mnv2_qnet):
    """A caller bypassing the batch former (novel batch shape straight
    into a stage executor) is a silent recompile-per-shape stall: the
    stage must warn, bump the metric, and surface in stats()."""
    clock = FakeClock(step=1e-3)
    reg = MetricsRegistry()
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                       tracer=Tracer(clock, origin_s=0.0), metrics=reg,
                       name="m")
    head = eng.stages[0]
    cu_name = head.spec.cu
    assert eng.stats().stage_retraces == {
        s.spec.cu: 0 for s in eng.stages}
    with pytest.warns(RuntimeWarning, match="retrace at non-bucketed"):
        head(jnp.asarray(_images(3), jnp.float32))  # 3 is not a bucket
    assert eng.stats().stage_retraces[cu_name] == 1
    key = f'serve_stage_retraces_total{{cu="{cu_name}",model="m"}}'
    assert reg.snapshot()["counters"][key] == 1
    # bucketed shapes stay silent
    head(jnp.asarray(_images(2), jnp.float32))
    assert eng.stats().stage_retraces[cu_name] == 1


# ---------------------------------------------------------------------------
# zero-completion / expiry snapshot safety
# ---------------------------------------------------------------------------


def test_stats_and_snapshot_defined_with_no_traffic(mnv2_qnet):
    clock = FakeClock(step=1e-3)
    reg = MetricsRegistry()
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                       tracer=Tracer(clock, origin_s=0.0), metrics=reg,
                       name="m")
    assert eng.run() == {}  # draining an empty queue is a no-op
    st = eng.stats()
    assert st.n_ok == 0 and st.pad_fraction == 0.0
    json.dumps(reg.snapshot(), allow_nan=False)


def test_all_expired_drain_closes_spans_and_counts(mnv2_qnet):
    clock = FakeClock(t0=100.0, step=1e-3)
    tracer = Tracer(clock, origin_s=100.0)
    reg = MetricsRegistry()
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock, tracer=tracer,
                       metrics=reg, name="m")
    rid = eng.submit(_images(1)[0], deadline_s=1.0)  # long past
    results = eng.run()
    assert results[rid].status == "expired"
    st = eng.stats()
    assert st.n_ok == 0 and st.n_expired == 1
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["counters"]['serve_requests_expired_total{model="m"}'] == 1
    assert snap["histograms"][
        'serve_request_latency_seconds{model="m"}']["p50"] is None
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []  # expiry closed the async span
    summary = summarize_trace(doc)
    assert summary["requests"]["by_status"] == {"expired": 1}


# ---------------------------------------------------------------------------
# multi-model: one shared timeline
# ---------------------------------------------------------------------------


def test_multimodel_shared_tracer_one_timeline(mnv2_qnet, effnet_qnet):
    clock = FakeClock(step=1e-3)
    tracer = Tracer(clock, origin_s=0.0)
    reg = MetricsRegistry()
    mm = MultiModelEngine({
        "mnv2": VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                             tracer=tracer, metrics=reg, name="mnv2"),
        "effnet": VisionEngine(effnet_qnet, buckets=(2,), clock=clock,
                               tracer=tracer, metrics=reg, name="effnet"),
    }, clock=clock)
    for img in _images(2):
        mm.submit("mnv2", img)
        mm.submit("effnet", img)
    results = mm.run()
    assert len(results) == 4
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    # per-model request categories keep rid 0/1 of each model distinct
    cats = {ev["cat"] for ev in events
            if ev.get("ph") == "b" and ev["name"] == "request"}
    assert cats == {"request:mnv2", "request:effnet"}
    summary = summarize_trace(doc)
    assert summary["requests"]["completed"] == 4
    # one router_dispatch instant per dispatch_log entry, counters agree
    instants = [ev for ev in events
                if ev["ph"] == "i" and ev["name"] == "router_dispatch"]
    assert len(instants) == len(mm.dispatch_log)
    per_model = {m: sum(1 for n, _ in mm.dispatch_log if n == m)
                 for m in ("mnv2", "effnet")}
    snap = reg.snapshot()
    for m, n in per_model.items():
        assert snap["counters"][f'router_dispatch_total{{model="{m}"}}'] == n


# ---------------------------------------------------------------------------
# autotune provenance spans
# ---------------------------------------------------------------------------


def _tiny_net():
    from repro.core import graph as G
    blocks = (
        G.BlockSpec("stem", (
            G.OpSpec("stem/conv", G.CONV, 3, 8, 3, 2, G.RELU6, 8, 4),)),
        G.BlockSpec("b1", (
            G.OpSpec("b1/expand", G.PW, 8, 16, 1, 1, G.RELU6, 4, 4),
            G.OpSpec("b1/dw", G.DW, 16, 16, 3, 1, G.RELU6, 4, 4),
            G.OpSpec("b1/project", G.PW, 16, 8, 1, 1, G.NONE, 4, 4),
        ), residual=True),
        G.BlockSpec("tail", (
            G.OpSpec("tail/pw", G.PW, 8, 16, 1, 1, G.RELU6, 4, 4),),
            avgpool=True),
        G.BlockSpec("classifier", (
            G.OpSpec("classifier/fc", G.DENSE, 16, 7, 1, 1, G.NONE, 4, 4),)),
    )
    return G.NetSpec(name="tiny", blocks=blocks, input_hw=16, input_ch=3,
                     num_classes=7)


def test_autotune_emits_provenance_spans():
    qnet = make_calibrated_qnet(_tiny_net())
    clock = FakeClock(step=1e-4)
    tracer = Tracer(clock, origin_s=0.0)

    def measure(fn, x, candidate=None):
        return 1.0

    plan = tune_qnet(qnet, batch=2, measure=measure, tracer=tracer)
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    spans = [ev for ev in events
             if ev["ph"] == "X" and ev["name"].startswith("tune:")]
    # one candidate-timing span per (key, candidate), each carrying the
    # measured-or-disqualified provenance
    assert len(spans) >= len(plan.entries)
    assert all("candidate" in ev["args"] and "disqualified" in ev["args"]
               for ev in spans)
    winners = [ev for ev in events
               if ev["ph"] == "i" and ev["name"] == "tune_winner"]
    assert len(winners) == len(plan.entries)  # one fresh selection per key
    assert ({ev["args"]["key"] for ev in winners}
            == set(plan.entries))
    # the autotune track is metadata-named
    assert any(ev["ph"] == "M" and ev["name"] == "thread_name"
               and ev["args"]["name"] == "autotune" for ev in events)


# ---------------------------------------------------------------------------
# trainer metrics + spans
# ---------------------------------------------------------------------------


def test_train_emits_metrics_and_phase_spans(tmp_path):
    # same net/batch geometry as tests/test_train_vision.CFG so the jitted
    # train step is already compiled when that module ran first
    cfg = V.VisionTrainConfig(
        model="mobilenet_v2", alpha=0.35, input_hw=16, num_classes=4,
        float_steps=2, qat_steps=4, batch=8, anneal_from=8,
        calibrate_every=2, ckpt_every=2)
    clock = FakeClock(step=1e-3)
    tracer = Tracer(clock, origin_s=0.0)
    reg = MetricsRegistry()
    result = V.train(cfg, ckpt_dir=str(tmp_path), tracer=tracer,
                     metrics=reg)
    assert result.done
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)
    assert snap["counters"]["train_steps_total"] == result.step
    assert snap["gauges"]["train_act_bits"] == 4.0  # final anneal stage
    assert snap["counters"]["train_calibration_rounds_total"] == len(
        result.history["calibration"])
    assert snap["histograms"]["train_checkpoint_seconds"]["count"] >= 1
    assert snap["gauges"]["train_loss"] is not None
    doc = tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    names = {ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    for ph in V.phase_schedule(cfg):
        assert f"phase:{ph.name}" in names
    assert "calibration_round" in names
    assert "checkpoint" in names
