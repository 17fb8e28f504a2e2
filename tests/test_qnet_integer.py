"""Integer QNet execution: per-op exactness, fixed-point requant, save/load."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cu, graph as G, qnet as Q
from repro.core.calibrate import ActObserver, relu6_fused_qparams
from repro.core.integer_ops import (
    quantize_multiplier, requantize_fixedpoint, requantize_float)
from repro.core.quant import QuantConfig, compute_scale_zp, dequantize
from repro.models import layers


def _quantize_one_op(op, p, x, bits_in=8):
    in_cfg = QuantConfig(bits_in, symmetric=False)
    s_in, z_in = compute_scale_zp(jnp.asarray(0.0), jnp.asarray(6.0), in_cfg)
    x_q = cu.quantize_input(x, float(s_in), float(z_in), bits_in)
    x_deq = (x_q.astype(jnp.float32) + float(z_in)) * float(s_in)
    y_fp = layers._apply_op(x_deq, op, p, qat=False)
    obs = {op.name: ActObserver.init(()).update(y_fp, QuantConfig(op.act_bits, False, None))}
    qops = {}
    Q._quantize_op(qops, {op.name: p}, op, float(s_in), float(z_in), obs)
    return qops[op.name], x_q, x_deq


@pytest.mark.parametrize("kind,act", [
    (G.CONV, G.RELU6), (G.DW, G.RELU6), (G.PW, G.RELU6),
    (G.PW, G.NONE), (G.DENSE, G.NONE),
])
def test_integer_op_matches_float_within_one_lsb(kind, act):
    key = jax.random.PRNGKey(0)
    if kind == G.DW:
        op = G.OpSpec("op", kind, 16, 16, 3, 1, act, 4, 4)
    elif kind in (G.CONV,):
        op = G.OpSpec("op", kind, 16, 32, 3, 1, act, 4, 4)
    else:
        op = G.OpSpec("op", kind, 16, 32, 1, 1, act, 4, 4)
    p = layers.init_op_params(key, op)
    x = jax.random.uniform(
        key, (4, op.in_ch) if kind == G.DENSE else (2, 8, 8, op.in_ch),
        minval=0, maxval=6)
    qop, x_q, x_deq = _quantize_one_op(op, p, x)
    y_int = cu._run_qop(x_q, qop, fixed_point=False)
    y_int_deq = (y_int.astype(jnp.float32) + round(qop.out_zp)) * qop.out_scale
    wcfg = QuantConfig(4, True, -1)
    w_deq = dequantize(jnp.asarray(qop.w_q, jnp.int32), jnp.asarray(qop.w_scale),
                       jnp.zeros_like(jnp.asarray(qop.w_scale)), wcfg)
    y_ref = layers._apply_op(x_deq, op, {"w": w_deq, "b": p["b"]}, qat=False)
    # two independent roundings (requant multiplier + folded bias) -> <= 1 LSB
    assert float(jnp.abs(y_int_deq - y_ref).max()) <= qop.out_scale * 1.01


def test_fixed_point_requant_matches_float():
    """The FPGA 'Approximator' (int mantissa + shift) == float multiplier."""
    rng = np.random.default_rng(0)
    acc = jnp.asarray(rng.integers(-(2**20), 2**20, (256,)), jnp.int32)
    mult = rng.uniform(1e-5, 0.5, (256,))
    mant, shift = quantize_multiplier(mult)
    y_float = requantize_float(acc, jnp.asarray(mult, jnp.float32))
    with jax.enable_x64(True):
        y_fxp = requantize_fixedpoint(
            acc.astype(jnp.int64), jnp.asarray(mant), jnp.asarray(shift))
    # mantissa has 31 bits: agree within 1 ULP of the requantized grid
    assert int(jnp.abs(y_float - y_fxp.astype(jnp.int32)).max()) <= 1


def test_relu6_fusion_is_integer_clip():
    """h^pq: [0,6] -> [0, 2^BW-1]; integer clip == ReLU6 after dequant."""
    cfg = QuantConfig(4, symmetric=False)
    s, z = relu6_fused_qparams(cfg)
    xs = jnp.linspace(-2, 8, 101)
    q = jnp.clip(jnp.round(xs / s - z), 0, cfg.qmax)
    deq = (q + z) * s
    relu6 = jnp.clip(xs, 0, 6)
    assert float(jnp.abs(deq - relu6).max()) <= float(s) * 0.5 + 1e-6


def test_qnet_save_load_roundtrip(tmp_path):
    from repro.models import mobilenet_v2 as mnv2
    from repro.core.calibrate import calibrate

    net = mnv2.build(alpha=0.35, input_hw=32, num_classes=10)
    params = layers.init_params(jax.random.PRNGKey(0), net)

    def apply_fn(p, b):
        return layers.forward(p, b, net, capture=True)[1]

    batches = [jax.random.uniform(jax.random.PRNGKey(i), (2, 32, 32, 3),
                                  minval=-1, maxval=1) for i in range(2)]
    obs = calibrate(apply_fn, params, batches, QuantConfig(4, False, None))
    qn = Q.quantize_net(params, net, obs)
    path = str(tmp_path / "qnet.bin")
    Q.save_qnet(qn, path)
    qn2 = Q.load_qnet(path, net)
    x = batches[0]
    y1 = cu.run_qnet(qn, x)
    y2 = cu.run_qnet(qn2, x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert qn2.res_q == qn.res_q


def test_qnet_model_size_compression():
    """Fig 13b: BW=4 model ~8x smaller than FP32 weights."""
    from repro.models import mobilenet_v2 as mnv2
    net = mnv2.build(alpha=0.35, input_hw=32, num_classes=10)
    fp32_bytes = net.n_params(with_bias=False) * 4
    q_bytes = net.model_bits(with_bias=False) / 8
    assert 7.0 < fp32_bytes / q_bytes <= 8.01
