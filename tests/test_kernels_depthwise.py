"""Pallas depthwise kernel vs pure-jnp oracle: shape/dtype/stride sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.depthwise_conv import depthwise_conv_q


def _mk(h, w, c, K, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 16, (2, h, w, c)), jnp.int32)
    wq = jnp.asarray(rng.integers(-7, 8, (K, K, c)), jnp.int32)
    mult = jnp.asarray(rng.uniform(0.001, 0.01, c), jnp.float32)
    zc = jnp.asarray(rng.uniform(0, 0.5, c), jnp.float32)
    b = jnp.asarray(rng.integers(-3, 3, c), jnp.int32)
    return x, wq, mult, zc, b


@pytest.mark.parametrize("h,w,c,K,s,bh", [
    (8, 8, 16, 3, 1, 8),
    (8, 8, 16, 3, 2, 16),
    (9, 9, 8, 3, 1, 8),       # odd spatial
    (11, 13, 8, 3, 2, 8),     # odd + rectangular + stride 2
    (12, 12, 32, 5, 1, 8),    # 5x5 kernel (EfficientNet)
    (10, 10, 24, 5, 2, 8),
    (16, 16, 128, 3, 1, 128), # one full 128-lane channel block
    (8, 8, 200, 3, 2, 2),     # two lane blocks, the second zero-padded
    (6, 6, 256, 3, 1, 4),     # two full lane blocks
])
def test_depthwise_matches_ref(h, w, c, K, s, bh):
    x, wq, mult, zc, b = _mk(h, w, c, K)
    y = depthwise_conv_q(x, wq, mult, zc, b, kernel=K, stride=s,
                         block_h=bh, interpret=True)
    yr = ref.depthwise_conv_q_ref(x, wq, mult, zc, b, kernel=K, stride=s)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@pytest.mark.parametrize("qmax", [7, 15, 63, 255])
def test_depthwise_bitwidth_sweep(qmax):
    """BW in {3,4,6,8}: clip bound == fused ReLU6 at that BW."""
    x, wq, mult, zc, b = _mk(8, 8, 16, 3)
    y = depthwise_conv_q(x, wq, mult, zc, b, qmax=qmax, interpret=True)
    yr = ref.depthwise_conv_q_ref(x, wq, mult, zc, b, qmax=qmax)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert int(y.max()) <= qmax and int(y.min()) >= 0


def test_depthwise_no_clip_linear_output():
    x, wq, mult, zc, b = _mk(8, 8, 8, 3)
    y = depthwise_conv_q(x, wq, mult, zc, b, clip=False, interpret=True)
    yr = ref.depthwise_conv_q_ref(x, wq, mult, zc, b, clip=False)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    assert int(y.min()) < 0  # linear path keeps negatives


try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st


@pytest.mark.parametrize("h,w,c,K,s,bh", [
    (16, 16, 8, 3, 1, 4),     # 4 row tiles
    (16, 16, 8, 3, 2, 2),     # stride 2: strip start walks 2x per tile
    (14, 14, 8, 5, 1, 3),     # 5x5 halo spans two neighbouring tiles
    (13, 11, 8, 5, 2, 2),     # 5x5 stride 2, odd rectangular
    (12, 12, 8, 3, 1, 1),     # one output row per tile (max grid)
    (9, 9, 8, 3, 2, 8),       # block_h > H_out: single tile fallback
    (10, 10, 16, 5, 2, 7),    # block_h not dividing H_out: shrinks to 5
])
def test_depthwise_row_tiling(h, w, c, K, s, bh):
    """Row-tiled grid (batch, channel_tiles, row_tiles): every tiling of the
    output rows — including strips whose K-1 halo crosses the in-kernel
    zero padding — agrees with the oracle bit-for-bit."""
    x, wq, mult, zc, b = _mk(h, w, c, K, seed=1)
    y = depthwise_conv_q(x, wq, mult, zc, b, kernel=K, stride=s,
                         block_h=bh, interpret=True)
    yr = ref.depthwise_conv_q_ref(x, wq, mult, zc, b, kernel=K, stride=s)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


@settings(max_examples=15, deadline=None)
@given(
    h=st.integers(6, 14), w=st.integers(6, 14),
    c=st.sampled_from([8, 16]), k=st.sampled_from([3, 5]),
    s=st.sampled_from([1, 2]), bh=st.sampled_from([1, 2, 3, 8]),
    seed=st.integers(0, 10_000),
)
def test_property_depthwise_random_geometry(h, w, c, k, s, bh, seed):
    """Hypothesis sweep: any (H, W, C, K, stride, row tile) agrees with the
    oracle — covers stride-2 and 5x5 (EfficientNet) geometries."""
    x, wq, mult, zc, b = _mk(h, w, c, k, seed=seed)
    y = depthwise_conv_q(x, wq, mult, zc, b, kernel=k, stride=s,
                         block_h=bh, interpret=True)
    yr = ref.depthwise_conv_q_ref(x, wq, mult, zc, b, kernel=k, stride=s)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
