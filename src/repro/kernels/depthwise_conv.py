"""Quantized depthwise convolution Pallas kernel (paper Sec. 4.1.1).

FPGA original: a 3D line buffer streams input rows; a K x K sliding window
with parallel read ports feeds K*K*N parallel MACs (Eq. 8); results pass the
Approximator & Clip unit.

TPU adaptation: depthwise conv has *no channel reduction*, so the natural TPU
mapping is (row-strip, channel)-tiled VMEM blocks with the K x K accumulation
fully unrolled as shifted vector multiplies over the (rows, cols) plane — the
VPU analogue of K*K*N parallel MACs; there is nothing for the MXU to do (that
is the paper's point: systolic arrays waste FMAs on depthwise).

Grid: (batch, channel_tiles, row_tiles). The wrapper applies the SAME zero
padding; each grid step then DMAs one input strip — its `block_h` output
rows' worth of input plus the K - stride halo rows — into VMEM through an
element-offset block (strips overlap by the halo, which a plain blocked
spec cannot express). That strip is the line buffer: VMEM holds
O(block_h * W * 128), never the whole plane, so a 112 x 112 layer fits.
Each of the K x K taps is a (strided, for stride 2) window read straight
from the strip ref; the unrolled multiply-accumulate runs on the VPU, the
per-channel requant epilogue follows, and the step writes
[block_h, W_out, 128].

The wrapper also zero-pads C up to a multiple of 128 and every block is
128 lanes wide: Mosaic takes element-offset blocks only on tile-aligned
lanes, and strided window reads only from a 128-lane ref. Depthwise inputs
are ReLU6-fused quantized (zero-point 0), so the zero padding is exact.

CU mapping (see README 'Performance'): this kernel is the DW op's compiled
path on TPU, and the Body CU's dw stage when the fused-IRB kernel does not
apply; off-TPU the same math runs as `integer_ops.int_depthwise_shifts`
(identical shifted-multiply accumulation, XLA-compiled).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import LANES, requant_clip, round_up, same_pad_amount


def _dw_kernel(x_ref, w_ref, mult_ref, zcorr_ref, bias_ref, o_ref,
               *, kernel: int, stride: int, th: int, w_out: int, qmax: int,
               clip: bool):
    # x_ref: [1, (th-1)*stride + K, Wp, bc] — this strip of the padded input
    bc = o_ref.shape[-1]
    acc = jnp.zeros((th, w_out, bc), jnp.int32)
    # K x K unrolled shifted multiply-accumulate == the sliding window
    for ki in range(kernel):
        for kj in range(kernel):
            patch = x_ref[0, pl.ds(ki, th, stride=stride),
                          pl.ds(kj, w_out, stride=stride), :]
            t = ki * kernel + kj
            acc = acc + patch * w_ref[t:t + 1, :]
    y = requant_clip(acc, mult_ref[...], zcorr_ref[...], bias_ref[...], qmax,
                     clip)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "stride", "qmax", "clip", "block_h",
                     "interpret"),
)
def depthwise_conv_q(
    x_q: jnp.ndarray,  # [B, H, W, C] int8/int32 quantized activations (zp folded)
    w_q: jnp.ndarray,  # [K, K, C] int8 symmetric per-channel weights
    mult: jnp.ndarray,  # [C] f32 requant multiplier S_x*S_w/S_y
    zcorr: jnp.ndarray,  # [C] f32 folded zero-point correction M*z_x*wsum
    bias_q: jnp.ndarray,  # [C] i32 bias in output units
    *,
    kernel: int = 3,
    stride: int = 1,
    qmax: int = 15,
    clip: bool = True,
    block_h: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas depthwise conv, SAME padding, grid (B, C_tiles, row_tiles).

    `block_h` output rows per grid step (shrunk to the largest divisor of
    H_out); channels run in 128-lane blocks. Returns int32 in [0, qmax].
    """
    b, h, w, c = x_q.shape
    ph_lo, ph_hi, h_out = same_pad_amount(h, kernel, stride)
    pw_lo, pw_hi, w_out = same_pad_amount(w, kernel, stride)
    cp = round_up(c, LANES)
    th = min(block_h, h_out)
    while h_out % th:
        th -= 1
    nrows = (th - 1) * stride + kernel  # strip rows incl. the halo
    xp = jnp.pad(x_q.astype(jnp.int32),
                 ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, cp - c)))
    wp = xp.shape[2]

    def lanes(v):  # [..., C] -> [..., Cp], zero-padded channels
        return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, cp - c)])

    el, bc = pl.Element, LANES
    grid = (b, cp // bc, h_out // th)
    out = pl.pallas_call(
        functools.partial(_dw_kernel, kernel=kernel, stride=stride, th=th,
                          w_out=w_out, qmax=qmax, clip=clip),
        grid=grid,
        in_specs=[
            # element offsets: strip j starts at padded row j*th*stride and
            # overlaps the next strip by the K - stride halo rows
            pl.BlockSpec((el(1), el(nrows), el(wp), el(bc)),
                         lambda i, k, j: (i, j * th * stride, 0, k * bc)),
            pl.BlockSpec((kernel * kernel, bc), lambda i, k, j: (0, k)),
            pl.BlockSpec((1, bc), lambda i, k, j: (0, k)),
            pl.BlockSpec((1, bc), lambda i, k, j: (0, k)),
            pl.BlockSpec((1, bc), lambda i, k, j: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, th, w_out, bc), lambda i, k, j: (i, j, 0, k)),
        out_shape=jax.ShapeDtypeStruct((b, h_out, w_out, cp), jnp.int32),
        interpret=interpret,
    )(xp, lanes(w_q.reshape(kernel * kernel, c).astype(jnp.int32)),
      lanes(mult.reshape(1, c)), lanes(zcorr.reshape(1, c)),
      lanes(bias_q.reshape(1, c)))
    return out[..., :c] if cp != c else out


__all__ = ["depthwise_conv_q"]
