"""Pallas pointwise-CU kernel vs the `int_pointwise` + epilogue reference.

Bit-exactness (array_equal, not allclose) is the bar: the kernel must be a
drop-in for the reference integer datapath on every PW/DENSE op.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.integer_ops import int_pointwise, quantized_op_epilogue
from repro.kernels.pointwise_conv import pointwise_conv_q

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st


def _mk(shape, cin, cout, *, in_qmax=15, wmax=7, zx=0.0, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, in_qmax + 1, (*shape, cin)), jnp.int32)
    w = jnp.asarray(rng.integers(-wmax, wmax + 1, (cin, cout)), jnp.int32)
    mult = jnp.asarray(rng.uniform(0.001, 0.01, cout), jnp.float32)
    bias = jnp.asarray(rng.integers(-3, 4, cout), jnp.int32)
    wsum = w.sum(0).astype(jnp.int32)
    zpc = (jnp.int32(zx) * wsum).astype(jnp.int32)
    return x, w, mult, zpc, bias, wsum, jnp.int32(zx)


def _ref(x, w, mult, bias, wsum, zx, qmax):
    return quantized_op_epilogue(
        int_pointwise(x, w), z_x=zx, wsum=wsum, bias_q=bias, mult=mult,
        qmax=qmax)


@pytest.mark.parametrize("shape,cin,cout,bm,bn,bk", [
    ((2, 8, 8), 256, 256, 32, 128, 128), # PW op on NHWC: M, N, K all tiled
    ((2, 7, 7), 24, 56, 16, 128, 128),   # odd spatial -> M padding
    ((4,), 48, 10, 128, 128, 128),       # DENSE op on [B, C] (classifier)
    ((1, 3, 5), 100, 36, 8, 128, 128),   # C_in/C_out with no 2^7 divisor
    ((2, 6, 6), 8, 1280, 64, 128, 128),  # wide tail pw
])
def test_pointwise_matches_int_pointwise(shape, cin, cout, bm, bn, bk):
    x, w, mult, zpc, bias, wsum, zx = _mk(shape, cin, cout)
    y = pointwise_conv_q(x, w, mult, zpc, bias, qmax=15, block_m=bm,
                         block_n=bn, block_k=bk, interpret=True)
    yr = _ref(x, w, mult, bias, wsum, zx, 15)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("zx", [-128, -7, 3, 117])
def test_pointwise_nonzero_input_zero_point(zx):
    """Post-residual PW inputs carry a nonzero zero point: the integer
    zpc = z_x * wsum correction must match the reference bit-for-bit."""
    x, w, mult, zpc, bias, wsum, jzx = _mk((2, 5, 5), 32, 24, zx=zx, seed=3)
    y = pointwise_conv_q(x, w, mult, zpc, bias, qmax=15, block_m=16,
                         block_n=128, block_k=128, interpret=True)
    yr = _ref(x, w, mult, bias, wsum, jzx, 15)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("act_bits", [4, 8])
def test_pointwise_bitwidth_sweep(act_bits):
    qmax = 2**act_bits - 1
    x, w, mult, zpc, bias, wsum, zx = _mk(
        (2, 6, 6), 16, 16, in_qmax=qmax, seed=1)
    y = pointwise_conv_q(x, w, mult, zpc, bias, qmax=qmax, block_m=32,
                         block_n=128, block_k=128, interpret=True)
    yr = _ref(x, w, mult, bias, wsum, zx, qmax)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert 0 <= int(y.min()) and int(y.max()) <= qmax


def test_pointwise_no_clip_linear_output():
    x, w, mult, zpc, bias, wsum, zx = _mk((2, 4, 4), 16, 8, seed=2)
    bias = bias - 10  # force negatives through
    y = pointwise_conv_q(x, w, mult, zpc, bias, qmax=15, clip=False,
                         block_m=16, block_n=128, block_k=128, interpret=True)
    acc = int_pointwise(x, w)
    yr = jnp.round(acc.astype(jnp.float32) * mult).astype(jnp.int32) + bias
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert int(y.min()) < 0


@settings(max_examples=15, deadline=None)
@given(
    h=st.integers(3, 9), b=st.integers(1, 3),
    cin=st.sampled_from([8, 24, 33]), cout=st.sampled_from([8, 17, 40]),
    act_bits=st.sampled_from([4, 8]), seed=st.integers(0, 10_000),
)
def test_property_pointwise_vs_int_pointwise(h, b, cin, cout, act_bits, seed):
    """Any geometry/bit-width: the Pallas kernel == int_pointwise + epilogue."""
    qmax = 2**act_bits - 1
    x, w, mult, zpc, bias, wsum, zx = _mk(
        (b, h, h), cin, cout, in_qmax=qmax, seed=seed)
    y = pointwise_conv_q(x, w, mult, zpc, bias, qmax=qmax, block_m=32,
                         block_n=128, block_k=128, interpret=True)
    yr = _ref(x, w, mult, bias, wsum, zx, qmax)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
