"""Fused IRB (Body CU) Pallas kernel vs oracle + vs the unfused CU runner."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fused_irb import fused_irb_q


def _mk(c, e, co, seed=0):
    rng = np.random.default_rng(seed)
    w1 = jnp.asarray(rng.integers(-7, 8, (c, e)), jnp.int32)
    w2 = jnp.asarray(rng.integers(-7, 8, (3, 3, e)), jnp.int32)
    w3 = jnp.asarray(rng.integers(-7, 8, (e, co)), jnp.int32)
    def mk(n, corr):
        return (
            jnp.asarray(rng.uniform(0.001, 0.01, n), jnp.float32),
            corr,
            jnp.asarray(rng.integers(-2, 3, n), jnp.int32),
        )
    # expand: integer z_x*wsum term (the block input may carry a nonzero
    # zero point); dw / project inputs are ReLU6 outputs (zero point 0)
    zpc1 = 3 * w1.sum(0).astype(jnp.int32)
    return (w1, w2, w3, mk(e, zpc1), mk(e, jnp.zeros(e, jnp.float32)),
            mk(co, jnp.zeros(co, jnp.int32)))


@pytest.mark.parametrize("h,w,c,e,co,s,res,bh", [
    (8, 8, 8, 32, 16, 1, False, 4),
    (8, 8, 16, 64, 16, 1, True, 8),
    (9, 9, 8, 24, 16, 2, False, 4),
    (12, 16, 16, 96, 24, 2, False, 3),
    (8, 8, 8, 48, 8, 1, True, 2),    # residual, small strips
    (16, 16, 24, 144, 32, 1, False, 16),  # MobileNet-ish geometry
])
def test_fused_irb_matches_ref(h, w, c, e, co, s, res, bh):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 16, (2, h, w, c)), jnp.int32)
    w1, w2, w3, (m1, c1, b1), (m2, c2, b2), (m3, c3, b3) = _mk(c, e, co)
    rc = (-1.0, 0.5, 2.0, 0.9, 1) if res else None
    y = fused_irb_q(x, w1, m1, c1, b1, w2, m2, c2, b2, w3, m3, c3, b3,
                    stride=s, residual=res, res_consts=rc, block_h=bh,
                    interpret=True)
    yr = ref.fused_irb_q_ref(x, w1, m1, c1, b1, w2, m2, c2, b2,
                             w3, m3, c3, b3, stride=s, residual=res,
                             res_scale=rc)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_fused_irb_equals_unfused_cu_execution():
    """The fused kernel must reproduce the unfused integer CU path exactly
    on a real quantized MobileNet-V2 block (paper's fusion-is-lossless claim)."""
    from repro.core import cu, qnet as Q
    from repro.core.calibrate import calibrate
    from repro.core.quant import QuantConfig
    from repro.kernels.ops import run_irb_block
    from repro.models import layers, mobilenet_v2 as mnv2

    net = mnv2.build(alpha=0.35, input_hw=32, num_classes=10)
    params = layers.init_params(jax.random.PRNGKey(0), net)

    def apply_fn(p, b):
        return layers.forward(p, b, net, capture=True)[1]

    batches = [jax.random.uniform(jax.random.PRNGKey(i), (2, 32, 32, 3),
                                  minval=-1, maxval=1) for i in range(2)]
    obs = calibrate(apply_fn, params, batches, QuantConfig(4, False, None))
    qn = Q.quantize_net(params, net, obs)

    # walk to the first 3-op IRB and compare fused kernel vs unfused run_block
    x = batches[0]
    first = qn.ops[net.blocks[0].ops[0].name]
    y = cu.quantize_input(x, first.in_scale, first.in_zp, 8)
    s, z = first.in_scale, first.in_zp
    checked = 0
    for block in net.blocks:
        if len(block.ops) == 3 and block.se is None:
            y_fused, fs, fz = run_irb_block(y, block, qn, s, z, interpret=True)
            y_ref, rs, rz = cu.run_block(y, block, qn, s, z)
            np.testing.assert_array_equal(np.asarray(y_fused), np.asarray(y_ref))
            assert (fs, fz) == (rs, rz)
            y, s, z = y_ref, rs, rz
            checked += 1
            if checked >= 3:
                break
        else:
            y, s, z = cu.run_block(y, block, qn, s, z)
    assert checked >= 3
