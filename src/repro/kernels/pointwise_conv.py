"""Quantized pointwise-convolution Pallas kernel (paper Sec. 4.1.3).

FPGA original: the pointwise CU is a matrix-multiply engine — a 1x1 conv has
no spatial window, so every output pixel is one row of an (H*W*B, C_in) x
(C_in, C_out) GEMM ("the design of this operator can be similar to the design
of a general matrix multiplication"). The Approximator & Clip unit requantizes
the int32 accumulator on the way out.

TPU adaptation: flatten the activations to [M, K] = [B*H*W, C_in] and tile an
M x N x K grid for the MXU with int8 operands and int32 accumulation. The k
axis is innermost, so each (i, j) output tile stays VMEM-resident while K
streams; the fused requant/clip epilogue runs once, on the last k step —
intermediate accumulators never visit HBM in anything but their final int
form. The same kernel serves:

  * PW ops (Head/Body expand+project, Tail pw)  — x is [B, H, W, C_in],
  * DENSE ops (Classifier)                      — x is [B, C_in],

i.e. every op the CU planner maps to a matmul engine.

MXU operands: activations are unsigned (x in [0, 255]) and the MXU takes
signed int8, so the kernel multiplies (x - 128) and the wrapper folds the
shift back into the integer zero-point correction (128 * wsum). Blocks obey
the TPU tiling rule: each of bn/bk is a multiple of 128 that divides its
dimension, else the whole dimension; bm is a multiple of 8 (M is padded).

Epilogue exactness: the kernel receives the INTEGER zero-point correction
`zpc = int32(z_x) * wsum` (per output channel) and computes

    y = clip( round((acc + zpc) * mult) + bias_q, 0, qmax )

which is operation-for-operation the float-multiplier branch of
`core.integer_ops.quantized_op_epilogue` — so the kernel is bit-exact with
the `int_pointwise` + epilogue reference, not merely allclose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import lane_block, requant_clip, round_up

# unsigned activations -> signed int8 MXU operands (see module docstring)
_X_SHIFT = 128


def _pw_kernel(x_ref, w_ref, mult_ref, zpc_ref, bias_ref, o_ref,
               *, nsteps: int, qmax: int, clip: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = (x_ref[...] - _X_SHIFT).astype(jnp.int8)  # [bm, bk]
    o_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.int32)

    @pl.when(k == nsteps - 1)
    def _epilogue():
        acc = o_ref[...] + zpc_ref[...]
        o_ref[...] = requant_clip(
            acc, mult_ref[...], jnp.float32(0.0), bias_ref[...], qmax, clip)


@functools.partial(
    jax.jit,
    static_argnames=("qmax", "clip", "block_m", "block_n", "block_k",
                     "interpret"),
)
def pointwise_conv_q(
    x_q: jnp.ndarray,  # [..., C_in] int activations in [0, 255]
    w_q: jnp.ndarray,  # [C_in, C_out] int8-range symmetric per-out-channel
    mult: jnp.ndarray,  # [C_out] f32 requant multiplier S_x*S_w/S_y
    zpc: jnp.ndarray,  # [C_out] i32 integer zero-point correction z_x*wsum
    bias_q: jnp.ndarray,  # [C_out] i32 bias in output units (z_y folded)
    *,
    qmax: int = 15,
    clip: bool = True,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas pointwise conv / dense matmul with the fused integer epilogue.

    Flattens leading dims to M and pads M up to a block multiple (pad rows
    are computed then discarded). `block_n`/`block_k` are caps: each block
    is the largest multiple of 128 within the cap that divides its
    dimension, else the whole dimension, so any channel count compiles.
    Returns int32 in [0, qmax] with the input's leading shape + [C_out].
    """
    lead = x_q.shape[:-1]
    k_dim = x_q.shape[-1]
    n_dim = w_q.shape[-1]
    x2 = x_q.reshape(-1, k_dim).astype(jnp.int32)
    m = x2.shape[0]

    bm = max(8, min(block_m, round_up(m, 8)) // 8 * 8)
    pad = (-m) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    mp = m + pad
    bn = lane_block(n_dim, block_n)
    bk = lane_block(k_dim, block_k)
    w8 = w_q.astype(jnp.int8)
    # sum_k (x - 128) w + 128 * wsum == sum_k x w: the shift rides in zpc
    wsum = jnp.sum(w_q.astype(jnp.int32), axis=0)
    zpc = zpc.astype(jnp.int32) + _X_SHIFT * wsum

    row = lambda v: v.reshape(1, n_dim)  # noqa: E731 — (1, N) lane vectors
    grid = (mp // bm, n_dim // bn, k_dim // bk)
    out = pl.pallas_call(
        functools.partial(_pw_kernel, nsteps=grid[2], qmax=qmax, clip=clip),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n_dim), jnp.int32),
        interpret=interpret,
    )(x2, w8, row(mult), row(zpc), row(bias_q))
    if pad:
        out = out[:m]
    return out.reshape(*lead, n_dim)


__all__ = ["pointwise_conv_q"]
