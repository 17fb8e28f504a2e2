"""The program's spans on the profile's clock, idle time named by them, stage
programs' device seconds, and the `place_us_per_image` reader, on hand-made
events; the existing readers pinned on the recorded fixture."""
import json
import random
from pathlib import Path
from types import SimpleNamespace

import pytest

import counts
import host_spans as H
import net
import run
import trace_reduce as T

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "effn_trace.pbtxt"


def profile_of(planes):
    """An XSpace text proto of {plane: {line: [(name, start_ns, end_ns)]}},
    read back as `jax.profiler.ProfileData` (as make_trace_fixture.py)."""
    import jax

    text = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        text.append(f"planes {{\n  id: {pid}\n  name: {json.dumps(plane)}")
        names = {}
        for lid, (line, evs) in enumerate(lines.items(), 1):
            text.append(f"  lines {{\n    id: {lid}\n    name: {json.dumps(line)}\n"
                        f"    timestamp_ns: 0")
            for name, s, e in evs:
                mid = names.setdefault(name, len(names) + 1)
                text.append(f"    events {{ metadata_id: {mid} offset_ps: {s * 1000} "
                            f"duration_ps: {(e - s) * 1000} }}")
            text.append("  }")
        for name, mid in names.items():
            text.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {json.dumps(name)} }} }}")
        text.append("}")
    return jax.profiler.ProfileData.from_text_proto("\n".join(text) + "\n")


OPS = [("%fusion.1 = f32[8] fusion()", 0, 10), ("%pad.2 = s32[8] pad()", 30, 40)]
NOTES = [("bench.drain", 5, 45), ("serve.drain", 6, 44), ("serve.place", 12, 28)]


def test_gap_inside_place_inside_drain_is_named_serve_place():
    profile = profile_of({"/device:TPU:0": {T.OPS_LINE: OPS}})
    red = T.reduce(profile, (0, 50), NOTES)
    # the gap 10-30 is covered by all three notes: the innermost names it
    assert dict((round(g * 1e9), n) for n, g in red.idle_gaps)[20] == "serve.place"
    assert H.idle_gaps(OPS, (0, 50), NOTES) == [
        ("serve.drain", 10, 12), ("serve.place", 12, 28), ("serve.drain", 28, 30),
        ("serve.drain", 40, 44), ("bench.drain", 44, 45),
        ("outside bench annotations", 45, 50)]


def test_idle_split_sums_to_idle_seconds():
    rng = random.Random(3)
    ops = []
    for _ in range(200):
        s = rng.randrange(0, 100_000)
        ops.append(("%fusion = f32[] fusion()", s, s + rng.randrange(1, 400)))
    notes = []
    for _ in range(300):
        s = rng.randrange(0, 100_000)
        notes.append((rng.choice(["serve.place", "serve.record", "bench.drain"]),
                      s, s + rng.randrange(1, 3_000)))
    window = (1_000, 99_000)
    profile = profile_of({"/device:TPU:0": {T.OPS_LINE: ops}})
    red = T.reduce(profile, window, notes)
    split = H.idle_by_label(ops, window, notes)
    assert sum(split.values()) == pytest.approx(red.window_s - red.busy_s, rel=1e-12)
    assert set(split) <= {"serve.place", "serve.record", "bench.drain",
                          "outside bench annotations"}


def test_stage_programs_reduce_to_seconds_per_stage():
    modules = [("jit_stage_head(11)", 0, 40), ("jit_stage_body(12)", 40, 100),
               ("jit_stage_head(11)", 100, 130), ("jit_stage_classifier(14)", 130, 140),
               ("jit_convert_element_type(3)", 140, 150)]
    profile = profile_of({"/device:TPU:0": {T.OPS_LINE: OPS, H.MODULES_LINE: modules},
                          "/host:CPU": {"python": [("jit_stage_tail", 0, 500)]}})
    execs = H.stage_executions(profile)
    assert {k: len(v) for k, v in execs.items()} == {"head": 2, "body": 1, "classifier": 1}
    got = H.stage_seconds(execs, (10, 135))  # clipped to the slice
    assert got == pytest.approx({"head": 60e-9, "body": 60e-9, "classifier": 5e-9})


def span(name, ts, dur, **args):
    ev = {"ph": "X", "name": name, "cat": "", "pid": 0, "tid": 0, "ts": ts, "dur": dur}
    if args:
        ev["args"] = args
    return ev


def doc_of(events, origin_unix_ns=None):
    doc = {"traceEvents": [{"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
                            "args": {"name": "p"}}] + events}
    if origin_unix_ns is not None:
        doc["otherData"] = {"origin_unix_ns": origin_unix_ns}
    return doc


def test_serve_notes_land_on_the_profile_clock():
    # tracer origin 2 us after the profile started; spans in us from origin
    doc = doc_of([span("drain", 0.0, 10.0), span("place", 1.0, 2.0, batch=0, rows=8),
                  span("record", 50.0, 1.0, batch=0, rows=8)], origin_unix_ns=1_000_002_000)
    notes = H.serve_notes(doc, 1_000_000_000, (0, 20_000))
    assert notes == [("serve.drain", 2_000, 12_000), ("serve.place", 3_000, 5_000)]
    assert H.serve_notes(doc_of(doc["traceEvents"][1:]), 0, (0, 1e12)) == []


def test_clock_agreement_pairs_dispatch_harvest_and_executions():
    doc = doc_of([span("dispatch:head", 1.0, 1.0, batch=0, rows=8),
                  span("dispatch:classifier", 3.0, 1.0, batch=0, rows=8),
                  span("dispatch:head", 4.0, 1.0, batch=1, rows=8),
                  span("dispatch:classifier", 6.0, 1.0, batch=1, rows=8),
                  span("harvest", 7.0, 3.0, batch=0), span("harvest", 10.0, 1.0, batch=1)],
                 origin_unix_ns=0)
    execs = {"head": [("jit_stage_head", 1_020, 2_000), ("jit_stage_head", 5_000, 5_500)],
             "classifier": [("jit_stage_classifier", 3_500, 9_000),
                            ("jit_stage_classifier", 9_000, 10_600)]}
    got = H.clock_agreement(doc, 0, execs, (0, 20_000))
    assert got["pairs:head"] == 2 and got["pairs:classifier"] == 2
    assert got["exec_minus_dispatch_us"] == pytest.approx((0.02, 3.0))
    assert got["harvest_minus_exec_us"] == pytest.approx((0.4, 1.0))
    assert got["harvests_paired"] == 2
    execs["head"] = execs["head"][:1]
    assert H.clock_agreement(doc, 0, execs, (0, 20_000))["pairs:head"] is None


def test_longest_span_and_intervals():
    doc = doc_of([span("drain", 0.0, 100.0),
                  span("form_batch", 1.0, 2.0, batch=0), span("place", 3.0, 1.0, batch=0),
                  span("dispatch:head", 30.0, 1.0, batch=0), span("record", 40.0, 9.0, batch=0),
                  span("drain", 300.0, 10.0), span("form_batch", 301.0, 1.0, batch=1)])
    got = H.longest(doc)
    assert got["span"] == ("record", pytest.approx(9e-6))
    assert got["in_drain"] == ("place -> dispatch:head", pytest.approx(26e-6))
    assert got["between_drains"] == ("drain 0 -> 1", pytest.approx(200e-6))


def _inputs(spans=(), trace=None, batches=()):
    return SimpleNamespace(spans=list(spans), trace=trace, traced_batches=list(batches))


def test_place_reader_by_hand():
    read = run.reader("place_us_per_image")
    form = [span("form_batch", 0.0, 5.0, bucket=8, batch=b) for b in range(3)]
    place = [span("place", 5.0, d, batch=b, rows=r)
             for b, (d, r) in enumerate([(40.0, 8), (40.0, 8), (20.0, 4)])]
    assert read(_inputs(form + place)) == pytest.approx(100.0 / 20)
    assert read(_inputs(form)) is None  # the parent program has no place spans
    assert read(_inputs(form + place[:2])) is None  # not one per micro-batch
    assert read(_inputs()) is None


def test_place_reader_reads_the_engines_spans():
    """The reader finds the serving engine's own `place` spans: one per
    micro-batch, one fake-clock step each over the bucket's rows."""
    import numpy as np

    from repro.models import mobilenet_v2
    from repro.models.layers import make_calibrated_qnet
    from repro.obs import Tracer
    from repro.serve.vision import MultiModelEngine, VisionEngine

    t = [0.0]

    def clock():
        t[0] += 1e-3
        return t[0]

    tracer = Tracer(clock, origin_s=0.0)
    eng = VisionEngine(make_calibrated_qnet(mobilenet_v2.build(
        alpha=0.35, input_hw=16, num_classes=4)), buckets=(2, 4), clock=clock,
        tracer=tracer, name="m")
    router = MultiModelEngine({"m": eng})
    for img in np.zeros((6, 16, 16, 3), np.float32):
        router.submit("m", img)
    router.run()
    read = run.reader("place_us_per_image")
    assert read(_inputs(tracer.to_chrome()["traceEvents"])) == pytest.approx(
        2 * 1e3 / (4 + 2))


@pytest.fixture(scope="module")
def fixture_inputs():
    import jax

    profile = jax.profiler.ProfileData.from_text_proto(FIXTURE.read_text())
    cfg = net.load_config(net.BENCH / "configs" / "efficientnet_compact-128-w4.json")

    def inputs(notes):
        return run.LayerInputs(
            blocks=net.family(cfg).blocks(cfg), input_hw=cfg["input_hw"],
            peak=counts.load_peak("TPU v5 lite"), macs_per_image=cfg["macs_per_image"],
            trace=T.reduce(profile, notes=notes), traced_batches=[8] * 16,
            traced_images=128, spans=[], chips=1)
    return profile, inputs


# the accepted readers' values on the recorded EfficientNet fixture
PINNED = {"idle_share": 80.48501101427065, "mfu": 0.07923259691404884,
          "depthwise_roofline": 17.40087006018498, "fused_irb_roofline": None,
          "dispatch_us_per_image": None, "queue_wait_p99_ms": None}


def test_serve_notes_change_no_existing_metric(fixture_inputs):
    """Program spans as `serve.*` notes inside the fixture's drains rename
    idle gaps, and move no existing per-layer reading."""
    profile, inputs = fixture_inputs
    notes = T.annotations(profile)
    serve = []
    for _, s, e in (n for n in notes if n[0] == "bench.drain"):
        step = (e - s) / 8
        serve += [("serve.drain", s + 1, e - 1)] + [
            (name, s + k * step, s + (k + 0.5) * step)
            for k, name in enumerate(["serve.form_batch", "serve.place"] * 4)]
    plain, traced = inputs(notes), inputs(notes + serve)
    for name, want in PINNED.items():
        got = [run.reader(name)(x) for x in (plain, traced)]
        assert got[0] == got[1] == (pytest.approx(want, rel=1e-12) if want else None), name
    assert traced.trace.busy_s == plain.trace.busy_s
    assert {n for n, _ in traced.trace.idle_gaps} - {n for n, _ in plain.trace.idle_gaps}
    lo, hi = next((s, e) for n, s, e in notes if n == T.WINDOW)
    ops = [ev for evs in T.device_ops(profile).values() for ev in evs]
    split = H.idle_by_label(ops, (lo, hi), [n for n in notes + serve if n[0] != T.WINDOW])
    assert sum(split.values()) == pytest.approx(
        plain.trace.window_s - plain.trace.busy_s, rel=1e-9)
