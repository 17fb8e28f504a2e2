"""PreparedQNet integer fast path: bit-exactness, zero per-call host
uploads, trace-count stability, integer residual, differential property
fuzz over random NetSpecs, and the quantized_linear block-size
regressions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core import cu, graph as G
from repro.core.integer_ops import (
    f32_accum_exact,
    int_conv2d,
    int_depthwise_shifts,
    int_residual_add,
    residual_fixed_consts,
)
from repro.models import efficientnet as effn, layers, mobilenet_v2 as mnv2
from repro.serve.vision import VisionEngine

HW = 32


def _make_qnet(net, seed=0):
    return layers.make_calibrated_qnet(net, seed=seed)


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
# integer fast-path formulations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,s", [(3, 1), (3, 2), (5, 1), (5, 2)])
def test_depthwise_shifts_matches_int_conv(k, s):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (2, 11, 13, 16)), jnp.int32)
    w = jnp.asarray(rng.integers(-127, 128, (k, k, 16)), jnp.int32)
    got = int_depthwise_shifts(x, w, stride=s)
    ref = int_conv2d(x, w.reshape(k, k, 1, 16), stride=s, groups=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_f32_accum_exact_bound():
    # 4-bit weights, tiny reduction: trivially exact
    assert f32_accum_exact(np.full((16, 8), 7, np.int8), 15)
    # adversarial: bound 255 * 127 * 600 > 2^24 must be rejected
    assert not f32_accum_exact(np.full((600, 4), 127, np.int8), 255)


def test_integer_residual_add_close_to_float():
    """14-bit mantissa skip-add tracks the float rescale within 1 LSB."""
    rng = np.random.default_rng(1)
    a_q = jnp.asarray(rng.integers(0, 16, (256,)), jnp.int32)
    b_q = jnp.asarray(rng.integers(0, 16, (256,)), jnp.int32)
    a_s, a_z, b_s, b_z, y_s, y_z = 0.11, -1.7, 0.27, 0.9, 0.31, -0.4
    consts = residual_fixed_consts(a_s, a_z, b_s, b_z, y_s, y_z)
    got = int_residual_add(a_q, b_q, consts, qmax=15)
    a = (a_q.astype(jnp.float32) + a_z) * (a_s / y_s)
    b = (b_q.astype(jnp.float32) + b_z) * (b_s / y_s)
    ref = jnp.clip(jnp.round(a + b) - round(y_z), 0, 15).astype(jnp.int32)
    assert int(jnp.abs(got - ref).max()) <= 1
    assert 0 <= int(got.min()) and int(got.max()) <= 15


# ---------------------------------------------------------------------------
# PreparedQNet: bit-exactness + device residency
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qnet_fixture", ["mnv2_qnet", "effnet_qnet"])
def test_prepared_run_qnet_bit_exact(qnet_fixture, request):
    qnet = request.getfixturevalue(qnet_fixture)
    pq = cu.prepare_qnet(qnet)
    x = jnp.asarray(_images(3))
    ref = np.asarray(cu.run_qnet(qnet, x))
    fast = np.asarray(cu.run_qnet(pq, x))
    np.testing.assert_array_equal(ref, fast)


def test_prepared_fixed_point_consistent(mnv2_qnet):
    pq = cu.prepare_qnet(mnv2_qnet)
    x = jnp.asarray(_images(2))
    ref = np.asarray(cu.run_qnet(mnv2_qnet, x, fixed_point=True))
    fast = np.asarray(cu.run_qnet(pq, x, fixed_point=True))
    np.testing.assert_array_equal(ref, fast)


def test_prepare_is_idempotent(mnv2_qnet):
    pq = cu.prepare_qnet(mnv2_qnet)
    assert cu.prepare_qnet(pq) is pq


def test_prepared_constants_are_device_arrays(mnv2_qnet):
    """Every constant a stage trace closes over is already a jax.Array —
    nothing left for jit to upload from host numpy at trace time."""
    pq = cu.prepare_qnet(mnv2_qnet)
    for pop in pq.ops.values():
        for field in ("w_q", "w_kern", "wsum", "bias_q", "mult", "zcorr",
                      "zpc", "z_x", "mantissa", "shift", "w_scale"):
            assert isinstance(getattr(pop, field), jax.Array), field
    for consts in pq.res_fixed.values():
        assert all(isinstance(c, int) for c in consts)


def test_stage_hot_loop_has_no_host_uploads(mnv2_qnet):
    """After warmup, serving micro-batches must not convert host numpy
    arrays (weights / requant constants) — only the input image enters via
    the engine. Monkeypatch-counts np.ndarray -> jnp conversions."""
    eng = VisionEngine(mnv2_qnet, buckets=(2,))
    eng.warmup()
    real_asarray = jnp.asarray
    uploads = []

    def counting_asarray(x, *a, **k):
        if isinstance(x, np.ndarray) and x.ndim > 0:
            uploads.append(x.shape)
        return real_asarray(x, *a, **k)

    jnp.asarray = counting_asarray
    try:
        for img in _images(4):
            eng.submit(img)
        eng.run()
    finally:
        jnp.asarray = real_asarray
    # the only host->device transfers are the micro-batch images themselves
    assert uploads == [(2, HW, HW, 3), (2, HW, HW, 3)], uploads


def test_stage_trace_count_stays_one_per_bucket(mnv2_qnet):
    eng = VisionEngine(mnv2_qnet, buckets=(2,))
    eng.warmup()
    for img in _images(8):
        eng.submit(img)
    eng.run()
    for img in _images(4, seed=9):
        eng.submit(img)
    eng.run()
    # one bucket -> one trace of the served program; the per-CU programs
    # are the lowering unit and serving never traces them
    assert eng.net.traces == 1
    assert all(s.traces == 0 for s in eng.stages)


def test_prepared_stages_bit_exact_with_reference_stages(mnv2_qnet):
    imgs = _images(5)
    fast = VisionEngine(mnv2_qnet, buckets=(1, 2, 4))
    slow = VisionEngine(mnv2_qnet, buckets=(1, 2, 4), prepare=False,
                        op_kernels="off", body_fast_path="off")
    out = {}
    for name, eng in (("fast", fast), ("slow", slow)):
        rids = [eng.submit(img) for img in imgs]
        res = eng.run()
        out[name] = np.stack([res[r].logits for r in rids])
    np.testing.assert_array_equal(out["fast"], out["slow"])


def test_op_kernels_flag_validation(mnv2_qnet):
    with pytest.raises(ValueError, match="op_kernels"):
        VisionEngine(mnv2_qnet, buckets=(1,), op_kernels="maybe")
    with pytest.raises(ValueError, match="fixed_point"):
        VisionEngine(mnv2_qnet, buckets=(1,), op_kernels="on",
                     fixed_point=True)


# ---------------------------------------------------------------------------
# differential property fuzz: random NetSpecs, fast path vs reference
# ---------------------------------------------------------------------------


ACT_CHOICES = (4, 6, 8)  # widths the mixed-precision search draws from


def _mixed_act_bits(net: G.NetSpec, plan: int) -> G.NetSpec:
    """Deterministically scatter per-op act bits from ACT_CHOICES over the
    net (plan is a base-3 digit stream), keeping the stem at 8 like the
    model builders do. plan=0 leaves the net uniform."""
    if plan == 0:
        return net
    alloc = {}
    for i, (_, op) in enumerate(net.all_ops()):
        alloc[op.name] = ACT_CHOICES[(plan >> (2 * i)) % len(ACT_CHOICES)]
    return G.with_op_act_bits(net, alloc)


def _rand_netspec(stem_ch: int, n_body: int, expand: int, kernel: int,
                  stride: int, bits: int, body_ch: int) -> G.NetSpec:
    """A small compile_net-compatible net: CONV stem -> IRB-ish body blocks
    (mixed DW kernel/stride, optional expansion, residual where shapes
    allow) -> PW+avgpool tail -> DENSE classifier."""
    blocks = [G.BlockSpec("stem", (
        G.OpSpec("stem/conv", G.CONV, 3, stem_ch, 3, 2, G.RELU6, 8, bits),))]
    in_ch = stem_ch
    for i in range(n_body + 1):  # +1: first IRB completes the Head
        name = f"irb{i}"
        ops = []
        hidden = in_ch * expand
        if expand != 1:
            ops.append(G.OpSpec(f"{name}/expand", G.PW, in_ch, hidden, 1, 1,
                                G.RELU6, bits, bits))
        # stride/kernel only vary on the first body block so later blocks
        # keep stride 1 and can exercise the residual skip-line
        s = stride if i == 0 else 1
        ops.append(G.OpSpec(f"{name}/dw", G.DW, hidden, hidden, kernel, s,
                            G.RELU6, bits, bits))
        ops.append(G.OpSpec(f"{name}/project", G.PW, hidden, body_ch, 1, 1,
                            G.NONE, bits, bits))
        residual = s == 1 and in_ch == body_ch
        blocks.append(G.BlockSpec(name, tuple(ops), residual=residual))
        in_ch = body_ch
    blocks.append(G.BlockSpec("tail", (
        G.OpSpec("tail/pw", G.PW, in_ch, 2 * body_ch, 1, 1, G.RELU6, bits,
                 bits),), avgpool=True))
    blocks.append(G.BlockSpec("classifier", (
        G.OpSpec("classifier/fc", G.DENSE, 2 * body_ch, 7, 1, 1, G.NONE,
                 bits, bits),)))
    return G.NetSpec(name="fuzz", blocks=tuple(blocks), input_hw=16,
                     num_classes=7)


@settings(max_examples=6, deadline=None)
@given(
    stem_ch=st.sampled_from([8, 16]),
    n_body=st.integers(1, 2),
    expand=st.sampled_from([1, 2]),
    kernel=st.sampled_from([3, 5]),
    stride=st.sampled_from([1, 2]),
    bits=st.sampled_from([4, 8]),
    body_ch=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**16),
    act_plan=st.integers(0, 2**20),
)
def test_fuzz_fast_path_matches_reference(stem_ch, n_body, expand, kernel,
                                          stride, bits, body_ch, seed,
                                          act_plan):
    """Differential property: for random small NetSpecs (mixed DW kernel /
    stride / 5x5 / residual / PER-OP heterogeneous act bits from {4,6,8}),
    the PreparedQNet fast path — eager AND jitted, float AND fixed-point
    requant — is bit-exact with the reference interpreter, and the full
    `verify_export` route chain (reference / prepared / stage executors /
    engine kernels) agrees bitwise. Catches per-op formulation drift (e.g.
    f32 reassociation under jit, requant chained at the wrong input width)
    that the two fixed model topologies miss."""
    from repro.train.vision import verify_export

    net = _mixed_act_bits(
        _rand_netspec(stem_ch, n_body, expand, kernel, stride, bits,
                      body_ch), act_plan)
    qnet = _make_qnet(net, seed=seed % 7)
    pq = cu.prepare_qnet(qnet)
    x = jnp.asarray(np.asarray(jax.random.uniform(
        jax.random.PRNGKey(seed), (2, 16, 16, 3), minval=-1, maxval=1)))
    ref = np.asarray(cu.run_qnet(qnet, x))
    np.testing.assert_array_equal(ref, np.asarray(cu.run_qnet(pq, x)))
    np.testing.assert_array_equal(
        ref, np.asarray(jax.jit(lambda t: cu.run_qnet(pq, t))(x)))
    ref_fx = np.asarray(cu.run_qnet(qnet, x, fixed_point=True))
    np.testing.assert_array_equal(
        ref_fx, np.asarray(cu.run_qnet(pq, x, fixed_point=True)))
    np.testing.assert_array_equal(
        ref_fx,
        np.asarray(jax.jit(
            lambda t: cu.run_qnet(pq, t, fixed_point=True))(x)))
    # 4-route conformance chain on the heterogeneous net (raises on drift)
    report = verify_export(qnet, np.asarray(x))
    assert {"reference", "prepared", "stage-executors",
            "engine"} <= set(report["routes"])


# ---------------------------------------------------------------------------
# quantized_linear block-size regressions
# ---------------------------------------------------------------------------


def test_quantized_linear_blockn_not_whole_n():
    """N=192 (not a multiple of 128) used to become ONE 192-wide block;
    now it tiles with the largest divisor <= 128 (96) and stays correct."""
    from repro.kernels import ref as kref
    from repro.kernels.ops import quantize_weight_for_matmul, quantized_linear

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    wfp = jnp.asarray(rng.normal(size=(64, 192)), jnp.float32)
    wq, sc = quantize_weight_for_matmul(wfp, bits=8)
    y = quantized_linear(x, wq, sc, bits=8)
    yr = kref.quant_matmul_ref(x, wq, sc, group_size=64)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-3)


def test_quantized_linear_non_pow2_group():
    """K and group without a power-of-two relationship to 512 still pick a
    valid block_k (the search may no longer crash or emit block 0)."""
    from repro.kernels import ref as kref
    from repro.kernels.ops import quantized_linear

    rng = np.random.default_rng(1)
    k, n, g = 96, 32, 6  # group = 16
    x = jnp.asarray(rng.normal(size=(8, k)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    sc = jnp.asarray(rng.uniform(0.005, 0.02, (g, n)), jnp.float32)
    y = quantized_linear(x, wq, sc, bits=8)
    yr = kref.quant_matmul_ref(x, wq, sc, group_size=k // g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-3)


def test_quantized_linear_degenerate_groups_raise_cleanly():
    """G > K means group == 0: previously a ZeroDivisionError from `k % 0`,
    now the shape error surfaces as quant_matmul's ValueError."""
    from repro.kernels.ops import quantized_linear

    x = jnp.ones((4, 2), jnp.float32)
    wq = jnp.ones((2, 8), jnp.int8)
    sc = jnp.ones((4, 8), jnp.float32)  # 4 scale groups for K=2
    with pytest.raises(ValueError):
        quantized_linear(x, wq, sc, bits=8)
