"""Fused Inverted-Residual-Block Pallas kernel — the Body CU (Sec. 4.2.3).

FPGA original: the Body CU executes pointwise(expand) -> depthwise ->
pointwise(project) *concurrently in a fused fashion*, streaming intermediate
feature maps through FIFOs so the t*C-expanded tensor never reaches DDR.

TPU adaptation: one `pallas_call` whose grid walks (batch, output-row strips).
Per grid step it:
  1. DMAs one input strip (its output rows' input plus the dw halo rows)
     into VMEM through an element-offset block — strips overlap by the
     halo, and VMEM never holds the whole plane, so a 112 x 112 block fits,
  2. expands it on the MXU (int8 operands, int32 accum) + requant/clip (ReLU6),
     with the pointwise kernel's integer zero-point correction (the block
     input may carry a nonzero zero point after a residual block),
  3. zero-masks halo positions (== the dw's SAME zero padding, exact because
     ReLU6-fused quantization has zero-point 0),
  4. runs the K x K depthwise accumulation (VPU) as strided window reads
     from a VMEM scratch copy of the expanded strip, one 128-lane channel
     group at a time,
  5. projects back down on the MXU + requant,
  6. optionally adds the skip-line, with the float arithmetic of
     `core.cu._residual_add`.

The expanded intermediate exists ONLY in VMEM — the exact analogue of the
paper's stream FIFOs. HBM traffic per block is input + output + weights
instead of input + output + 2 x t-times-expanded intermediates; see
benchmarks/bench_fusion.py for the traffic accounting.

Layout: the wrapper zero-pads every channel dimension (C, E, C_out) up to a
multiple of 128 lanes and the strip width up to a multiple of 8 sublanes,
so the (rows, W, C) -> (rows * W, C) collapse before each matmul keeps the
TPU's (8, 128) tiling; pad lanes carry zero weights and are sliced off.
As in the pointwise kernel, activations enter the MXU as (x - 128) int8
and 128 * wsum restores the product; both matmul epilogues then compute
round((acc + z_x * wsum) * mult) + bias, operation for operation the
reference `quantized_op_epilogue`, and the depthwise one round(acc * mult
+ zcorr) + bias like the depthwise kernel (its input zero point is 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, requant_clip, round_up, same_pad_amount

_X_SHIFT = 128  # unsigned activations -> signed int8 MXU operands


def _mxu(x, w_ref, zpc_ref):
    """[n, K] int activations @ int8 [K, N] -> exact int32 [n, N]
    accumulator, zero-point corrected (zpc_ref holds z_x*wsum + 128*wsum)."""
    acc = jnp.dot((x - _X_SHIFT).astype(jnp.int8), w_ref[...],
                  preferred_element_type=jnp.int32)
    return acc + zpc_ref[...]


def _irb_kernel(
    x_ref,
    w1_ref, z1_ref, m1_ref, b1_ref,
    w2_ref, m2_ref, c2_ref, b2_ref,
    w3_ref, z3_ref, m3_ref, b3_ref,
    o_ref,
    e_ref, d_ref,
    *,
    kernel: int,
    stride: int,
    th: int,
    w_out: int,
    h: int,
    w: int,
    pad_top: int,
    pad_left: int,
    qmax: int,
    residual: bool,
    res_consts,
):
    _, nrows, wq, cp = x_ref.shape
    ep = w1_ref.shape[-1]
    row0 = pl.program_id(1) * th * stride

    # ---- 1-2. input strip -> pointwise expansion (MXU) ----
    acc1 = _mxu(x_ref[0].reshape(nrows * wq, cp), w1_ref, z1_ref)
    e = requant_clip(acc1, m1_ref[...], jnp.float32(0.0), b1_ref[...], qmax,
                     clip=True).reshape(nrows, wq, ep)

    # ---- 3. zero-mask halo rows/cols (the dw SAME padding; zp == 0) ----
    grow = row0 + jax.lax.broadcasted_iota(jnp.int32, e.shape, 0)
    gcol = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
    valid = (
        (grow >= pad_top) & (grow < pad_top + h)
        & (gcol >= pad_left) & (gcol < pad_left + w)
    )
    e = jnp.where(valid, e, 0)

    # ---- 4. depthwise K x K per 128-lane group (VPU) ----
    for g in range(ep // LANES):
        lanes = slice(g * LANES, (g + 1) * LANES)
        e_ref[g] = e[:, :, lanes]
        acc2 = jnp.zeros((th, w_out, LANES), jnp.int32)
        for ki in range(kernel):
            for kj in range(kernel):
                t = ki * kernel + kj
                patch = e_ref[g, pl.ds(ki, th, stride=stride),
                              pl.ds(kj, w_out, stride=stride), :]
                acc2 = acc2 + patch * w2_ref[t:t + 1, lanes]
        d = requant_clip(acc2, m2_ref[:, lanes], c2_ref[:, lanes],
                         b2_ref[:, lanes], qmax, clip=True)
        d_ref[:, lanes] = d.reshape(th * w_out, LANES)

    # ---- 5. pointwise projection (MXU) ----
    acc3 = _mxu(d_ref[...], w3_ref, z3_ref)
    y = requant_clip(acc3, m3_ref[...], jnp.float32(0.0), b3_ref[...], qmax,
                     clip=True).reshape(th, w_out, -1)

    # ---- 6. skip-line (residual path, Fig. 3; stride 1, C == C_out) ----
    if residual:
        a_z, r_a, b_z, r_b, zy = res_consts
        a = x_ref[0, pl.ds(pad_top, th), pl.ds(pad_left, w_out), :]
        a = (a.astype(jnp.float32) + a_z) * r_a
        yb = (y.astype(jnp.float32) + b_z) * r_b
        y = jnp.clip(jnp.round(a + yb) - zy, 0, qmax).astype(jnp.int32)

    o_ref[0] = y


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernel", "stride", "qmax", "residual", "res_consts", "block_h", "interpret",
    ),
)
def fused_irb_q(
    x_q: jnp.ndarray,  # [B, H, W, C] quantized activations in [0, 255]
    w1_q: jnp.ndarray,  # [C, E]   expand
    mult1, zpc1, bias1,  # [E] f32 / i32 z_x*wsum / i32
    w2_q: jnp.ndarray,  # [K, K, E] depthwise
    mult2, zcorr2, bias2,  # [E] f32 / f32 M*z_x*wsum / i32
    w3_q: jnp.ndarray,  # [E, Co]  project
    mult3, zpc3, bias3,  # [Co] f32 / i32 z_x*wsum / i32
    *,
    kernel: int = 3,
    stride: int = 1,
    qmax: int = 15,
    residual: bool = False,
    res_consts=None,  # (a_z, a_s/y_s, b_z, b_s/y_s, round(y_z)) static
    block_h: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, w, c = x_q.shape
    e_ch = w1_q.shape[-1]
    c_out = w3_q.shape[-1]
    ph_lo, ph_hi, h_out = same_pad_amount(h, kernel, stride)
    pw_lo, pw_hi, w_out = same_pad_amount(w, kernel, stride)
    th = min(block_h, h_out)
    while h_out % th:
        th -= 1
    nrows = (th - 1) * stride + kernel  # strip rows incl. the halo
    wo8 = round_up(w_out, 8)
    wq = round_up(max(pw_lo + w + pw_hi, (wo8 - 1) * stride + kernel), 8)
    cp, ep, cop = (round_up(n, LANES) for n in (c, e_ch, c_out))
    xp = jnp.pad(x_q.astype(jnp.int32),
                 ((0, 0), (ph_lo, ph_hi), (pw_lo, wq - pw_lo - w), (0, cp - c)))

    def pad2(a, rows, cols):
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))

    def vec(v, n):  # [n0] -> (1, n) zero-padded lane vector
        return pad2(v.reshape(1, -1), 1, n)

    def mat(wq_, zpc, rows, cols):  # int8 MXU weights + z_x*wsum + 128*wsum
        w32 = wq_.astype(jnp.int32)
        zpc = zpc.astype(jnp.int32) + _X_SHIFT * jnp.sum(w32, axis=0)
        return pad2(w32, rows, cols).astype(jnp.int8), vec(zpc, cols)

    w1, z1 = mat(w1_q, zpc1, cp, ep)
    w3, z3 = mat(w3_q, zpc3, ep, cop)
    w2 = pad2(w2_q.reshape(kernel * kernel, e_ch).astype(jnp.int32),
              kernel * kernel, ep)

    kern = functools.partial(
        _irb_kernel, kernel=kernel, stride=stride, th=th, w_out=wo8, h=h,
        w=w, pad_top=ph_lo, pad_left=pw_lo, qmax=qmax, residual=residual,
        res_consts=res_consts)
    el = pl.Element
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, j: (0,) * len(shape))
    out = pl.pallas_call(
        kern,
        grid=(b, h_out // th),
        in_specs=[
            # element offsets: strip j starts at padded row j*th*stride and
            # overlaps the next strip by the K - stride halo rows
            pl.BlockSpec((el(1), el(nrows), el(wq), el(cp)),
                         lambda i, j: (i, j * th * stride, 0, 0)),
            whole(cp, ep), whole(1, ep), whole(1, ep), whole(1, ep),
            whole(kernel * kernel, ep), whole(1, ep), whole(1, ep),
            whole(1, ep),
            whole(ep, cop), whole(1, cop), whole(1, cop), whole(1, cop),
        ],
        out_specs=pl.BlockSpec((1, th, wo8, cop), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h_out, wo8, cop), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((ep // LANES, nrows, wq, LANES), jnp.int32),
            pltpu.VMEM((th * wo8, ep), jnp.int32),
        ],
        interpret=interpret,
    )(
        xp,
        w1, z1, vec(mult1, ep), vec(bias1, ep),
        w2, vec(mult2, ep), vec(zcorr2, ep), vec(bias2, ep),
        w3, z3, vec(mult3, cop), vec(bias3, cop),
    )
    return out[:, :, :w_out, :c_out]


__all__ = ["fused_irb_q"]
