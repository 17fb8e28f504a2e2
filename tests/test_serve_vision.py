"""Vision serving subsystem: stage compiler correctness, pipelined
bit-exactness vs the monolithic integer runner, bucket admission edge cases,
deadline handling, deterministic fake-clock stress tests (EDF under expiry,
padding tails, bounded queue, NaN-safe stats, multi-model routing/fairness,
sharded multi-replica serving), and a queue-drain throughput smoke test."""
import math
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler as CC, cu
from repro.dist.sharding import data_mesh
from repro.models import efficientnet as effn, mobilenet_v2 as mnv2
from repro.models.layers import make_calibrated_qnet
from repro.obs import MetricsRegistry, Tracer
from repro.serve.vision import (
    AdmissionError,
    CompiledNet,
    MultiModelEngine,
    PipelinedExecutor,
    VisionEngine,
    compile_stages,
)

HW = 32


class FakeClock:
    """Deterministic injectable time source: every read ticks by `step`
    (so completion order is observable in latencies), plus manual
    `advance` for deadline scenarios — no wall-clock sleeps anywhere."""

    def __init__(self, t0: float = 0.0, step: float = 0.0):
        self.t = t0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _make_qnet(net, seed=0):
    return make_calibrated_qnet(net, seed=seed)


@pytest.fixture(scope="module")
def mnv2_qnet():
    return _make_qnet(mnv2.build(alpha=0.35, input_hw=HW, num_classes=10))


@pytest.fixture(scope="module")
def effnet_qnet():
    return _make_qnet(effn.build_compact(input_hw=HW, num_classes=10))


def _images(n, seed=7):
    return np.asarray(jax.random.uniform(
        jax.random.PRNGKey(seed), (n, HW, HW, 3), minval=-1, maxval=1))


# ---------------------------------------------------------------------------
# stage compiler
# ---------------------------------------------------------------------------


def test_stage_signatures_mobilenet(mnv2_qnet):
    plan = CC.compile_net(mnv2_qnet.spec)
    sigs = plan.stage_signatures()
    assert [s.cu for s in sigs] == [CC.HEAD, CC.BODY, CC.TAIL, CC.CLASSIFIER]
    head, body, tail, clf = sigs
    assert head.in_hw == HW and head.in_ch == 3
    # stage boundaries chain: out of one == in of the next
    assert (head.out_hw, head.out_ch) == (body.in_hw, body.in_ch)
    assert (body.out_hw, body.out_ch) == (tail.in_hw, tail.in_ch)
    assert tail.out_hw is None  # spatially collapsed by the global pool
    assert clf.out_ch == 10
    assert body.invocations == 16  # the paper's 16 Body CU invocations


def test_stage_quantizer_handoff_is_static(mnv2_qnet):
    stages = compile_stages(mnv2_qnet)
    # (scale, zp) contract chains across stages and matches the data-free
    # propagation from QNet metadata
    s, z = cu.input_qparams(mnv2_qnet)
    for st in stages:
        assert (st.spec.in_scale, st.spec.in_zp) == (s, z)
        s, z = cu.propagate_qparams(st.spec.blocks, mnv2_qnet, s, z)
        assert (st.spec.out_scale, st.spec.out_zp) == (s, z)


def test_run_blocks_matches_run_qnet(mnv2_qnet):
    x = jnp.asarray(_images(2))
    in_s, in_z = cu.input_qparams(mnv2_qnet)
    y = cu.quantize_input(x, in_s, in_z, 8)
    y, s, z = cu.run_blocks(y, mnv2_qnet.spec.blocks, mnv2_qnet, in_s, in_z)
    got = (y.astype(jnp.float32) + z) * s
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(cu.run_qnet(mnv2_qnet, x)))


def test_fusable_irb_gate():
    from repro.core.graph import DW, PW, RELU6, NONE, BlockSpec, OpSpec
    from repro.kernels.ops import fusable_irb

    def blk(act_bits3=4):
        return BlockSpec("b", (
            OpSpec("b/expand", PW, 8, 48, 1, 1, RELU6, 4, 4),
            OpSpec("b/dw", DW, 48, 48, 3, 1, RELU6, 4, 4),
            OpSpec("b/project", PW, 48, 16, 1, 1, NONE, 4, act_bits3),
        ))

    assert fusable_irb(blk())
    # mixed act_bits: the kernel's single-qmax clip would be wrong
    assert not fusable_irb(blk(act_bits3=8))


def test_noncontiguous_schedule_rejected(mnv2_qnet):
    plan = CC.compile_net(mnv2_qnet.spec)
    # interleave: head, body, head, body... breaks role contiguity
    sched = list(plan.schedule)
    sched[1], sched[2] = sched[2], sched[1]  # head, body, head, ...
    bad = CC.CUPlan(plan.net, tuple(sched))
    with pytest.raises(ValueError, match="non-contiguous"):
        bad.stage_groups()


# ---------------------------------------------------------------------------
# pipelined execution: bit-exactness vs the monolithic runner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qnet_fixture", ["mnv2_qnet", "effnet_qnet"])
def test_pipelined_bit_exact_with_run_qnet(qnet_fixture, request):
    qnet = request.getfixturevalue(qnet_fixture)
    imgs = _images(5)
    eng = VisionEngine(qnet, buckets=(1, 2, 4))
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    ref = np.asarray(cu.run_qnet(qnet, jnp.asarray(imgs)))
    np.testing.assert_array_equal(got, ref)
    assert all(results[r].status == "ok" for r in rids)


def test_fixed_point_refuses_fused_fast_path(mnv2_qnet):
    """The fused IRB kernel has no fixed-point requant mode: forcing it on
    together with fixed_point must fail loudly, and 'auto' must fall back
    to the exact unfused path."""
    with pytest.raises(ValueError, match="fixed_point"):
        compile_stages(mnv2_qnet, fixed_point=True, body_fast_path="on")
    stages = compile_stages(mnv2_qnet, fixed_point=True,
                            body_fast_path="auto")
    assert all(not s._fast_path for s in stages)


def test_pipelined_bit_exact_fixed_point(mnv2_qnet):
    """The FPGA-faithful fixed-point requant path through the stages."""
    imgs = _images(3)
    eng = VisionEngine(mnv2_qnet, buckets=(4,), fixed_point=True)
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    ref = np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs),
                                 fixed_point=True))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.slow
def test_pipelined_bit_exact_fused_body(mnv2_qnet):
    """Body CU through the fused Pallas IRB kernel (interpret mode on CPU)
    is still bit-exact with the monolithic reference."""
    imgs = _images(2)
    eng = VisionEngine(mnv2_qnet, buckets=(2,), body_fast_path="on",
                       interpret=not jax.default_backend() == "tpu")
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    ref = np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs)))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.slow
@pytest.mark.parametrize("qnet_fixture", ["mnv2_qnet", "effnet_qnet"])
def test_pipelined_bit_exact_op_kernels(qnet_fixture, request):
    """Every PW/DENSE op through the Pallas pointwise-CU kernel and every DW
    op through the row-tiled depthwise kernel (interpret mode on CPU):
    full-net logits stay identical to the monolithic reference."""
    qnet = request.getfixturevalue(qnet_fixture)
    imgs = _images(2)
    eng = VisionEngine(qnet, buckets=(2,), op_kernels="on",
                       interpret=not jax.default_backend() == "tpu")
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    ref = np.asarray(cu.run_qnet(qnet, jnp.asarray(imgs)))
    np.testing.assert_array_equal(got, ref)


def test_pipeline_executor_ordering(mnv2_qnet):
    stages = compile_stages(mnv2_qnet)
    pipe = PipelinedExecutor(stages)
    batches = [jnp.asarray(_images(2, seed=i)) for i in range(5)]
    outs = pipe.run(batches)
    assert len(outs) == 5
    for x, y in zip(batches, outs):
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(cu.run_qnet(mnv2_qnet, x)))


def test_pipeline_stream_abandoned_mid_drain_does_not_leak(mnv2_qnet):
    """Breaking out of stream() mid-drain must drop the in-flight batches:
    a later drain on the same executor must not replay stale tags."""
    stages = compile_stages(mnv2_qnet)
    pipe = PipelinedExecutor(stages)
    batches = [jnp.asarray(_images(2, seed=i)) for i in range(3)]
    for _ in pipe.stream(enumerate(batches)):
        break  # abandon with batches still in flight
    assert not pipe.busy
    outs = pipe.run(batches)  # fresh drain: exactly these 3, nothing stale
    assert len(outs) == 3
    np.testing.assert_array_equal(
        np.asarray(outs[0]),
        np.asarray(cu.run_qnet(mnv2_qnet, batches[0])))


# ---------------------------------------------------------------------------
# one program per bucket, two micro-batches in flight
# ---------------------------------------------------------------------------

NET_BUCKETS = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def net_refs(mnv2_qnet, effnet_qnet):
    """Eight images per model and their `cu.run_qnet` logits (eager, one
    batch: rows are independent, so any prefix is the reference for the
    same prefix of images)."""
    imgs = _images(8, seed=3)
    return {name: (imgs, np.asarray(cu.run_qnet(q, jnp.asarray(imgs))))
            for name, q in (("mnv2_qnet", mnv2_qnet),
                            ("effnet_qnet", effnet_qnet))}


@pytest.fixture(scope="module")
def net_engines(mnv2_qnet, effnet_qnet):
    return {"mnv2_qnet": VisionEngine(mnv2_qnet, buckets=NET_BUCKETS),
            "effnet_qnet": VisionEngine(effnet_qnet, buckets=NET_BUCKETS)}


@pytest.mark.parametrize("bucket", NET_BUCKETS)
@pytest.mark.parametrize("qnet_fixture", ["mnv2_qnet", "effnet_qnet"])
def test_one_program_bit_exact_at_every_bucket(qnet_fixture, bucket,
                                               net_engines, net_refs):
    """`bucket` images form one unpadded micro-batch of exactly that
    bucket; the whole-network program's logits equal `cu.run_qnet`'s."""
    eng = net_engines[qnet_fixture]
    imgs, ref = net_refs[qnet_fixture]
    rids = [eng.submit(img) for img in imgs[:bucket]]
    results = eng.run()
    assert eng.stats().pad_fraction == 0.0
    got = np.stack([results[r].logits for r in rids])
    np.testing.assert_array_equal(got, ref[:bucket])


def test_net_program_chains_the_stage_contracts(mnv2_qnet):
    """The served program spans the CU chain: the first stage's input
    contract, the last stage's output contract, every block once."""
    stages = compile_stages(mnv2_qnet)
    net = CompiledNet(stages)
    assert net.spec.cu == "net" and net.stages == tuple(stages)
    assert (net.spec.in_scale, net.spec.in_zp) == (
        stages[0].spec.in_scale, stages[0].spec.in_zp)
    assert (net.spec.out_scale, net.spec.out_zp) == (
        stages[-1].spec.out_scale, stages[-1].spec.out_zp)
    assert net.spec.quantizes_input and net.spec.dequantizes_output
    assert net.spec.blocks == mnv2_qnet.spec.blocks
    with pytest.raises(ValueError, match="at least one stage"):
        CompiledNet([])


def _traced_engine(qnet, clock, **kw):
    tracer = Tracer(clock, origin_s=0.0)
    reg = MetricsRegistry()
    eng = VisionEngine(qnet, buckets=(2,), clock=clock, tracer=tracer,
                       metrics=reg, name="m", **kw)
    return eng, tracer, reg


def test_one_dispatch_span_per_micro_batch(mnv2_qnet):
    """A traced drain of 3 micro-batches launches the whole network once
    per batch (`dispatch:net`), and each CU still counts one invocation
    per micro-batch."""
    eng, tracer, _ = _traced_engine(mnv2_qnet, FakeClock(step=1e-3))
    for img in _images(6):
        eng.submit(img)
    eng.run()
    spans = [ev for ev in tracer.to_chrome()["traceEvents"]
             if ev["ph"] == "X" and ev["name"].startswith("dispatch:")]
    assert [ev["name"] for ev in spans] == ["dispatch:net"] * 3
    assert [ev["args"]["batch"] for ev in spans] == [0, 1, 2]
    assert [ev["args"]["rows"] for ev in spans] == [2, 2, 2]
    stats = eng.stats()
    assert stats.stage_invocations == {
        st.spec.cu: 3 for st in eng.stages}
    assert set(stats.stage_invocations) == {
        CC.HEAD, CC.BODY, CC.TAIL, CC.CLASSIFIER}


def test_one_program_retrace_leak_warns(mnv2_qnet):
    """A non-bucket shape straight into the served program warns, bumps
    its counter, and counts against every CU (all of their code is
    retraced); bucketed shapes stay silent."""
    eng, _, reg = _traced_engine(mnv2_qnet, FakeClock(step=1e-3))
    with pytest.warns(RuntimeWarning, match="retrace at non-bucketed"):
        eng.net(jnp.asarray(_images(3), jnp.float32))  # 3 is not a bucket
    assert eng.stats().stage_retraces == {st.spec.cu: 1 for st in eng.stages}
    key = 'serve_stage_retraces_total{cu="net",model="m"}'
    assert reg.snapshot()["counters"][key] == 1
    eng.net(jnp.asarray(_images(2), jnp.float32))
    assert eng.net.retraces == 1


def test_harvest_waits_for_the_next_launch(mnv2_qnet):
    """Two micro-batches in flight: batch k's harvest starts after batch
    k+1's `dispatch:net` span (the device has the next batch queued while
    the host records this one); only the last batch of the drain is
    harvested without a launch behind it. Results come back in
    submission order, bit-exact."""
    eng, tracer, _ = _traced_engine(mnv2_qnet, FakeClock(step=1e-3))
    imgs = _images(8)
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    spans = [ev for ev in tracer.to_chrome()["traceEvents"]
             if ev["ph"] == "X"]

    def starts(name):
        return {ev["args"]["batch"]: ev["ts"] for ev in spans
                if ev["name"] == name}

    dispatch, harvest, record = (starts(n) for n in
                                 ("dispatch:net", "harvest", "record"))
    assert sorted(dispatch) == sorted(harvest) == [0, 1, 2, 3]
    for k in range(3):
        assert harvest[k] > dispatch[k + 1]
    assert harvest[3] > dispatch[3]
    assert [b for b, _ in sorted(record.items(), key=lambda kv: kv[1])] \
        == [0, 1, 2, 3]
    got = np.stack([results[r].logits for r in rids])
    np.testing.assert_array_equal(
        got, np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs))))


class _Program:
    """A stand-in stage program: records launches, returns its input."""

    def __init__(self, cu_name, log):
        self.spec = types.SimpleNamespace(cu=cu_name)
        self._log = log

    def __call__(self, x):
        self._log.append((self.spec.cu, x))
        return x


def _drive(pipe, n):
    """Tick the executor the way the router does (advance, inject, then
    harvest); returns (tick, tag) per returned batch."""
    out, tick, pending = [], 0, list(range(n))
    while pending or pipe.busy:
        finished = pipe.advance()
        if pending:
            pipe.inject((pending[0], pending.pop(0)))
        if finished is not None:
            out.append((tick, pipe.harvest(finished)[0]))
        tick += 1
    return out


def test_window_returns_a_batch_after_the_next_launch():
    """One program: `advance` returns batch k only once batch k+1 is
    launched, and at once when nothing new was injected (the flush)."""
    log = []
    pipe = PipelinedExecutor([_Program("net", log)])
    pipe.inject(("a", 0))
    assert pipe.advance() is None and pipe.busy  # a launched, alone
    pipe.inject(("b", 1))
    assert pipe.advance()[0] == "a"  # b launched behind it
    assert [x for _, x in log] == [0, 1]
    assert pipe.advance()[0] == "b"  # flush: nothing new injected
    assert pipe.advance() is None and not pipe.busy
    assert _drive(pipe, 4) == [(2, 0), (3, 1), (4, 2), (5, 3)]


@pytest.mark.parametrize("depth", [2, 3, 4])
def test_staged_executor_returns_a_batch_the_tick_it_finishes(depth):
    """Several stages: a batch comes back on the tick its last stage is
    launched (tick depth + k for batch k), as before the window."""
    log = []
    pipe = PipelinedExecutor([_Program(f"s{i}", log) for i in range(depth)])
    assert _drive(pipe, 5) == [(depth + k, k) for k in range(5)]
    assert len(log) == 5 * depth and not pipe.busy


def test_one_program_stream_abandoned_leaves_nothing_in_flight(mnv2_qnet):
    """Breaking out of stream() with two batches in flight drops both: a
    later drain returns exactly its own batches."""
    pipe = PipelinedExecutor([CompiledNet(compile_stages(mnv2_qnet))])
    batches = [jnp.asarray(_images(2, seed=i)) for i in range(4)]
    for _ in pipe.stream(enumerate(batches)):
        break  # batch 1 still in flight, batch 2 injected
    assert not pipe.busy
    outs = pipe.run(batches[:3])
    assert len(outs) == 3
    for x, y in zip(batches, outs):
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(cu.run_qnet(mnv2_qnet, x)))


def test_router_drain_over_two_engines_returns_every_result(
        mnv2_qnet, effnet_qnet, net_refs):
    """Several micro-batches per model through the router's tick loop,
    each model with two in flight: every request comes back ok and
    bit-exact, and both pipelines end empty."""
    clock = FakeClock(step=1e-4)
    mm = MultiModelEngine({
        "mnv2_qnet": VisionEngine(mnv2_qnet, buckets=(2,), clock=clock),
        "effnet_qnet": VisionEngine(effnet_qnet, buckets=(2,), clock=clock),
    }, clock=clock)
    n = {"mnv2_qnet": 6, "effnet_qnet": 4}  # 3 and 2 micro-batches
    handles = [(mm.submit(m, img, now=0.0), i)
               for m, k in n.items() for i, img in enumerate(net_refs[m][0][:k])]
    results = mm.run()
    assert set(results) == {h for h, _ in handles}
    for h, i in handles:
        assert results[h].status == "ok"
        np.testing.assert_array_equal(results[h].logits, net_refs[h[0]][1][i])
    assert not any(e.pipe.busy for e in mm.engines.values())
    assert sorted(m for m, _ in mm.dispatch_log) == \
        ["effnet_qnet"] * 2 + ["mnv2_qnet"] * 3


# ---------------------------------------------------------------------------
# bucket admission edge cases
# ---------------------------------------------------------------------------


def test_odd_tail_is_bucket_padded(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(2, 4))
    imgs = _images(7)  # -> 4 + 4(pad 1) under EDF draining
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    stats = eng.stats()
    assert stats.n_ok == 7
    assert stats.micro_batches == 2
    assert stats.pad_fraction == pytest.approx(1 / 8)
    ref = np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs)))
    got = np.stack([results[r].logits for r in rids])
    np.testing.assert_array_equal(got, ref)  # pad rows never leak


def test_single_request_uses_smallest_bucket(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(1, 2, 4))
    eng.submit(_images(1)[0])
    eng.run()
    assert eng.stats().pad_fraction == 0.0


def test_mixed_shapes_rejected(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(2,))
    eng.submit(_images(1)[0])
    with pytest.raises(AdmissionError, match="shape"):
        eng.submit(np.zeros((HW // 2, HW // 2, 3), np.float32))
    with pytest.raises(AdmissionError, match="shape"):
        eng.submit(np.zeros((HW, HW, 4), np.float32))
    with pytest.raises(AdmissionError, match="dtype"):
        eng.submit(np.zeros((HW, HW, 3), np.uint8))
    assert eng.pending() == 1  # rejected work never queued


def test_queue_bound(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(2,), max_queue=2)
    img = _images(1)[0]
    eng.submit(img)
    eng.submit(img)
    with pytest.raises(AdmissionError, match="queue full"):
        eng.submit(img)


def test_expired_deadline_dropped(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(2,))
    img = _images(1)[0]
    past = time.perf_counter() - 10.0
    dead = eng.submit(img, deadline_s=past)
    live = eng.submit(img)
    results = eng.run()
    assert results[dead].status == "expired"
    assert results[dead].logits is None
    assert results[live].status == "ok"
    stats = eng.stats()
    assert stats.n_expired == 1 and stats.n_ok == 1


def test_edf_orders_batches(mnv2_qnet):
    """Tighter deadlines are served in earlier micro-batches."""
    eng = VisionEngine(mnv2_qnet, buckets=(2,))
    img = _images(1)[0]
    now = time.perf_counter()
    eng.submit(img, deadline_s=now + 1000)  # loose deadline
    tight = eng.submit(img, deadline_s=now + 100)
    nodeadline = eng.submit(img)
    results = eng.run()
    # tight + loose share the first bucket-2 batch; no-deadline rides last
    assert results[tight].latency_s <= results[nodeadline].latency_s
    assert all(r.status == "ok" for r in results.values())


# ---------------------------------------------------------------------------
# deterministic fake-clock stress tests
# ---------------------------------------------------------------------------


def test_fake_clock_expiry_is_deterministic(mnv2_qnet):
    """Deadline expiry is decided against the injected clock at batch-form
    time — no sleeps, no wall-clock racing."""
    clock = FakeClock(t0=100.0)
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock)
    img = _images(1)[0]
    dead = eng.submit(img, deadline_s=50.0)   # already past the fake now
    live = eng.submit(img, deadline_s=200.0)
    later = eng.submit(img, deadline_s=101.0)
    clock.advance(5.0)  # 105.0: 'later' expires before the drain
    results = eng.run()
    assert results[dead].status == "expired"
    assert results[later].status == "expired"
    assert results[live].status == "ok"
    stats = eng.stats()
    assert (stats.n_ok, stats.n_expired) == (1, 2)
    assert stats.micro_batches == 1  # expired requests burn no CU work


def test_fake_clock_edf_dispatch_order(mnv2_qnet):
    """Tighter deadlines land in earlier micro-batches: with a ticking
    clock, completion times (latencies from a common arrival) are ordered
    exactly by deadline tightness, batch by batch."""
    clock = FakeClock(t0=0.0, step=1e-4)
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock)
    img = _images(1)[0]
    # submit in scrambled order; all share arrival now=0
    d = {eng.submit(img, deadline_s=dl, now=0.0): dl
         for dl in (300.0, 110.0, 150.0, 120.0)}
    results = eng.run()
    assert all(r.status == "ok" for r in results.values())
    # sort rids by their deadline; EDF packs [110,120] then [150,300]
    by_deadline = sorted(d, key=lambda r: d[r])
    lat = [results[r].latency_s for r in by_deadline]
    assert lat[0] == lat[1] < lat[2] == lat[3], lat


def test_fake_clock_padding_tail(mnv2_qnet):
    """5 requests over (2, 4) buckets: one full 4-bucket + a padded 2-bucket
    (deterministic — the fake clock never expires anything mid-drain)."""
    clock = FakeClock(t0=0.0)
    eng = VisionEngine(mnv2_qnet, buckets=(2, 4), clock=clock)
    for img in _images(5):
        eng.submit(img)
    results = eng.run()
    stats = eng.stats()
    assert stats.n_ok == 5
    assert stats.micro_batches == 2
    assert stats.pad_fraction == pytest.approx(1 / 6)
    assert all(r.status == "ok" for r in results.values())


def test_bounded_queue_frees_capacity_after_drain(mnv2_qnet):
    clock = FakeClock()
    eng = VisionEngine(mnv2_qnet, buckets=(2,), max_queue=2, clock=clock)
    img = _images(1)[0]
    eng.submit(img)
    eng.submit(img)
    with pytest.raises(AdmissionError, match="queue full"):
        eng.submit(img)
    eng.run()
    assert eng.pending() == 0
    eng.submit(img)  # drained queue admits again


def test_all_expired_stats_nan_safe(mnv2_qnet):
    """Regression: when every request expires there are zero completions —
    stats() must report NaN percentiles (not a misleading 0.0 or a
    divide-by-zero) and keep every ratio finite."""
    clock = FakeClock(t0=1000.0)
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock)
    for img in _images(3):
        eng.submit(img, deadline_s=1.0)  # all long past
    results = eng.run()
    assert all(r.status == "expired" for r in results.values())
    stats = eng.stats()
    assert stats.n_ok == 0 and stats.n_expired == 3
    assert math.isnan(stats.latency_p50_s)
    assert math.isnan(stats.latency_p95_s)
    assert stats.fps == 0.0
    assert stats.pad_fraction == 0.0
    assert stats.micro_batches == 0
    stats.as_dict()  # stays serializable


# ---------------------------------------------------------------------------
# multi-model routing
# ---------------------------------------------------------------------------


@pytest.fixture()
def router(mnv2_qnet, effnet_qnet):
    clock = FakeClock(t0=0.0, step=1e-4)
    return MultiModelEngine({
        "mnv2": VisionEngine(mnv2_qnet, buckets=(2,), clock=clock),
        "effnet": VisionEngine(effnet_qnet, buckets=(2,), clock=clock),
    }, clock=clock), clock


def test_multi_model_bit_exact_and_tagged(router, mnv2_qnet, effnet_qnet):
    mm, clock = router
    imgs = _images(4)
    handles = [mm.submit("mnv2" if i % 2 == 0 else "effnet", img, now=0.0)
               for i, img in enumerate(imgs)]
    results = mm.run()
    assert all(results[h].status == "ok" for h in handles)
    refs = {"mnv2": np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs))),
            "effnet": np.asarray(cu.run_qnet(effnet_qnet, jnp.asarray(imgs)))}
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(results[h].logits, refs[h[0]][i])
    stats = mm.stats()
    assert set(stats) == {"mnv2", "effnet"}
    assert stats["mnv2"].n_ok == stats["effnet"].n_ok == 2


def test_multi_model_unknown_model_rejected(router):
    mm, _ = router
    with pytest.raises(AdmissionError, match="unknown model"):
        mm.submit("resnet", _images(1)[0])


def test_multi_model_mixed_clocks_rejected(mnv2_qnet, effnet_qnet):
    """Wall time, latencies, and deadlines must share ONE time source: the
    router refuses engines holding different clocks unless an explicit
    clock= unifies them (which is propagated down)."""
    with pytest.raises(ValueError, match="clock"):
        MultiModelEngine({
            "a": VisionEngine(mnv2_qnet, buckets=(2,), clock=FakeClock()),
            "b": VisionEngine(effnet_qnet, buckets=(2,), clock=FakeClock()),
        })
    shared = FakeClock()
    mm = MultiModelEngine({
        "a": VisionEngine(mnv2_qnet, buckets=(2,), clock=FakeClock()),
        "b": VisionEngine(effnet_qnet, buckets=(2,), clock=FakeClock()),
    }, clock=shared)
    assert all(e._clock is shared for e in mm.engines.values())


def test_multi_model_fairness_round_robin(router):
    """Deadline-less load from two models interleaves one micro-batch per
    model per scheduler round — neither model starves the other."""
    mm, _ = router
    for i, img in enumerate(_images(8)):
        mm.submit("mnv2" if i < 4 else "effnet", img, now=0.0)
    results = mm.run()
    assert all(r.status == "ok" for r in results.values())
    order = [m for m, _ in mm.dispatch_log]
    assert sorted(order) == ["effnet", "effnet", "mnv2", "mnv2"]
    # strict alternation: a model never dispatches twice in a row
    assert all(a != b for a, b in zip(order, order[1:])), order


def test_multi_model_edf_prioritizes_tight_deadlines(router):
    """The model holding the tightest next deadline dispatches first into
    the shared device stream, regardless of name order."""
    mm, clock = router
    img = _images(1)[0]
    # effnet sorts first by name — give mnv2 the tighter deadlines to show
    # EDF (not name order) decides
    for _ in range(2):
        mm.submit("effnet", img, deadline_s=1e6, now=0.0)
        mm.submit("mnv2", img, deadline_s=10.0, now=0.0)
    results = mm.run()
    assert all(r.status == "ok" for r in results.values())
    assert mm.dispatch_log[0][0] == "mnv2", mm.dispatch_log


# ---------------------------------------------------------------------------
# sharded multi-replica serving
# ---------------------------------------------------------------------------


def test_sharded_single_replica_mesh_is_bit_exact(mnv2_qnet):
    """mesh over 1 device: the degenerate sharded path must match the
    monolithic reference exactly (and keep every bucket unchanged)."""
    imgs = _images(4)
    eng = VisionEngine(mnv2_qnet, buckets=(1, 2, 4), mesh=data_mesh(1))
    assert eng.buckets == (1, 2, 4) and eng.replicas == 1
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    np.testing.assert_array_equal(
        got, np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs))))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
def test_sharded_multi_replica_bit_exact(mnv2_qnet):
    """Micro-batches sharded across the 'data' mesh produce logits
    bit-identical to the single-device engine, and every requested bucket
    is rounded up to a replica multiple at construction."""
    n = 2 * (len(jax.devices()) // 2)
    mesh = data_mesh(n)
    eng = VisionEngine(mnv2_qnet, buckets=(1, 2, 4, n, 2 * n), mesh=mesh)
    assert all(b % n == 0 for b in eng.buckets)
    assert eng.replicas == n
    imgs = _images(2 * n)
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    got = np.stack([results[r].logits for r in rids])
    np.testing.assert_array_equal(
        got, np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(imgs))))
    assert eng.stats().replicas == n


def test_sharded_buckets_round_up_to_replica_multiples(mnv2_qnet):
    if len(jax.devices()) < 2:
        with pytest.raises(ValueError, match="replicas"):
            data_mesh(2)
        return
    eng = VisionEngine(mnv2_qnet, buckets=(1, 3, 4), mesh=data_mesh(2))
    assert eng.buckets == (2, 4)  # 1 -> 2, 3 -> 4 (merged), 4 stays
    img = _images(1)[0]
    rid = eng.submit(img)
    res = eng.run()
    np.testing.assert_array_equal(
        res[rid].logits,
        np.asarray(cu.run_qnet(mnv2_qnet, jnp.asarray(img[None])))[0])


# ---------------------------------------------------------------------------
# queue-drain throughput smoke test
# ---------------------------------------------------------------------------


def test_queue_drain_throughput_smoke(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(4,))
    eng.warmup()
    imgs = _images(16)
    rids = [eng.submit(img) for img in imgs]
    results = eng.run()
    stats = eng.stats()
    assert sorted(results) == sorted(rids)
    assert stats.n_ok == 16
    assert stats.fps > 0
    assert stats.micro_batches == 4
    # every CU stage invoked exactly once per micro-batch (warmup excluded)
    assert all(v == stats.micro_batches
               for v in stats.stage_invocations.values())
    assert stats.macs_per_image == mnv2_qnet.spec.count_macs()
    assert stats.energy_j_per_image > 0
    assert stats.watts >= stats.fps * stats.energy_j_per_image
    assert stats.fps_per_watt > 0
    d = stats.as_dict()
    assert {"fps", "latency_p50_s", "fps_per_watt", "watts",
            "power_source", "energy_tuned_fraction"} <= set(d)


# ---------------------------------------------------------------------------
# power-capped dispatch (docs/energy.md): deterministic fake-clock stress
# ---------------------------------------------------------------------------


def _fat_energy(j_per_image: float, idle_w: float = 0.0):
    """Synthetic EnergyReport with an exact J/image — the governor tests
    need batch energies that dominate the budget, not mnv2's real uJ."""
    from repro.energy import EnergyReport, OpEnergy, PowerModel

    op = OpEnergy(name="fat", cu="body", kind="pw", key="", us=1.0,
                  source="analytic", macs=1, bytes_moved=1,
                  compute_j=j_per_image, memory_j=0.0)
    return EnergyReport(net="fake", backend="cpu",
                        power=PowerModel(busy_w=max(10.0, idle_w + 1.0),
                                         idle_w=idle_w, source="test"),
                        ops=(op,))


def test_power_cap_stays_under_budget_zero_high_slo_drops(mnv2_qnet):
    """The acceptance stress: 1 J/image, 10 W budget over a 1 s window ->
    at most 2 bucket-4 batches per window. The governor must (a) keep the
    modeled watts under budget at every dispatch point, (b) shed ONLY the
    shed class (slo <= 0), (c) serve every slo-1 request eventually —
    zero drops above the shed class."""
    clock = FakeClock(step=1e-4)
    eng = VisionEngine(mnv2_qnet, buckets=(4,), clock=clock,
                       energy=_fat_energy(1.0), power_budget_w=10.0,
                       power_window_s=1.0, shed_slo=0)
    imgs = _images(12)
    rids = {eng.submit(img, slo=i % 2): i % 2
            for i, img in enumerate(imgs)}
    results = {}
    for _ in range(8):  # drain over advancing windows
        results.update(eng.run())
        assert eng._governor.watts(clock.t) <= 10.0 + 1e-9
        if not eng.pending():
            break
        clock.advance(0.5)
    assert not eng.pending()
    by_status = {}
    for rid, slo in rids.items():
        by_status.setdefault(results[rid].status, []).append(slo)
    # every shed request was sheddable; every slo-1 request came back ok
    assert set(by_status.get("shed", [])) <= {0}
    assert all(results[rid].status == "ok"
               for rid, slo in rids.items() if slo == 1)
    stats = eng.stats()
    assert stats.n_shed == len(by_status.get("shed", []))
    assert stats.n_deferred > 0  # the cap actually bit
    assert stats.power_budget_w == 10.0
    # shed results carry no logits; ok results are bit-exact
    for rid, slo in rids.items():
        if results[rid].status == "ok":
            ref = np.asarray(cu.run_qnet(
                mnv2_qnet, jnp.asarray(imgs[list(rids).index(rid)][None])))
            np.testing.assert_array_equal(results[rid].logits, ref[0])
        else:
            assert results[rid].logits is None


def test_power_cap_generous_budget_never_sheds(mnv2_qnet):
    clock = FakeClock(step=1e-4)
    eng = VisionEngine(mnv2_qnet, buckets=(4,), clock=clock,
                       energy=_fat_energy(1e-3), power_budget_w=100.0)
    rids = [eng.submit(img, slo=0) for img in _images(8)]
    results = eng.run()
    assert all(results[r].status == "ok" for r in rids)
    stats = eng.stats()
    assert stats.n_shed == 0 and stats.n_deferred == 0


def test_power_cap_deferred_requests_keep_deadlines(mnv2_qnet):
    """Deferral is not terminal and preserves EDF ordering: a deferred
    request with a live deadline is served on the next window; one whose
    deadline passes while deferred expires (not sheds)."""
    clock = FakeClock(step=1e-4)
    eng = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                       energy=_fat_energy(1.0), power_budget_w=6.0,
                       power_window_s=1.0, shed_slo=-1)  # nothing sheddable
    imgs = _images(6)
    now = clock.t
    r_live = eng.submit(imgs[0], slo=1, deadline_s=now + 100.0)
    r_tight = eng.submit(imgs[1], slo=1, deadline_s=now + 0.3)
    rest = [eng.submit(img, slo=1) for img in imgs[2:]]
    results = dict(eng.run())  # EDF serves r_tight first; budget defers tail
    for _ in range(6):
        if not eng.pending():
            break
        clock.advance(0.6)
        results.update(eng.run())
    assert results[r_tight].status == "ok"  # tight deadline went first
    assert results[r_live].status == "ok"
    # everything else either completed or expired while deferred — but
    # nothing was shed (shed_slo=-1) and nothing vanished
    assert set(results) == {r_live, r_tight, *rest}
    assert all(results[r].status in ("ok", "expired") for r in rest)
    assert eng.stats().n_shed == 0


def test_power_budget_must_clear_idle_floor(mnv2_qnet):
    with pytest.raises(ValueError):
        VisionEngine(mnv2_qnet, buckets=(2,),
                     energy=_fat_energy(1.0, idle_w=5.0),
                     power_budget_w=4.0)  # budget below idle draw


def test_multi_model_shared_power_budget(mnv2_qnet, effnet_qnet):
    """One governor spans the fleet: both models' dispatches debit the
    same rolling window, and the shared watt estimate stays capped."""
    clock = FakeClock(step=1e-4)
    engines = {
        "m": VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                          energy=_fat_energy(1.0), name="m"),
        "e": VisionEngine(effnet_qnet, buckets=(2,), clock=clock,
                          energy=_fat_energy(1.0), name="e"),
    }
    router = MultiModelEngine(engines, power_budget_w=5.0)
    assert engines["m"]._governor is router.governor
    assert engines["e"]._governor is router.governor
    handles = [router.submit("m" if i % 2 == 0 else "e", img, slo=1)
               for i, img in enumerate(_images(8))]
    results = dict(router.run())
    for _ in range(8):
        if not any(e.pending() for e in engines.values()):
            break
        assert router.governor.watts(clock.t) <= 5.0 + 1e-9
        clock.advance(1.0)
        results.update(router.run())
    assert all(results[h].status == "ok" for h in handles)
    assert router.governor.total_j > 0


def test_multi_model_refuses_double_governor(mnv2_qnet, effnet_qnet):
    clock = FakeClock()
    owned = VisionEngine(mnv2_qnet, buckets=(2,), clock=clock,
                         energy=_fat_energy(1.0), power_budget_w=10.0)
    other = VisionEngine(effnet_qnet, buckets=(2,), clock=clock,
                         energy=_fat_energy(1.0))
    with pytest.raises(ValueError):
        MultiModelEngine({"a": owned, "b": other}, power_budget_w=5.0)
