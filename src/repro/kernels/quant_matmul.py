"""Weight-quantized matmul Pallas kernel (paper Sec. 4.1.3 + Sec. 3.2).

This is DeepDive's pointwise-convolution CU generalized to every linear
operator in the assigned LM architectures: per-output-channel (or K-grouped)
low-bit weights are stored packed in HBM, streamed to VMEM, dequantized
in-register, and fed to the MXU — "the design of this operator can be similar
to the design of a general matrix multiplication" (Sec. 4.1.3), with the
paper's range-based linear quantization supplying the scales.

Supports BW=8 (int8 weights) and BW=4 (two nibbles per uint8, unpacked
in-kernel). Grid: (M/bm, N/bn, K/bk) with output-block accumulation —
the k axis is innermost so each (i, j) output tile stays resident while the
MXU streams K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import pack_int4, unpack_int4  # noqa: F401  (re-export)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *, bits: int, nsteps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # [bm, bk]
    if bits == 4:
        w_q = unpack_int4(w_ref[...], signed=True)  # [bk, bn] (packed on n)
    else:
        w_q = w_ref[...].astype(jnp.int32)
    # per-(k-group, n) scale for this k block — dequant BEFORE the MXU dot
    w = w_q.astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_m", "block_n", "block_k", "interpret"),
)
def quant_matmul(
    x: jnp.ndarray,  # [M, K] float (bf16/f32)
    w_q: jnp.ndarray,  # int8 [K, N] or packed uint8 [K, N//2] when bits == 4
    w_scale: jnp.ndarray,  # [G, N] per-k-group scales (G = K // group_size; G=1 => per-channel)
    *,
    bits: int = 8,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    m, k = x.shape
    n = w_q.shape[1] * (2 if bits == 4 else 1)
    g = w_scale.shape[0]
    if k % g:
        raise ValueError(f"K={k} not divisible by scale groups G={g}")
    group = k // g
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if bk % group and group % bk:
        raise ValueError(f"block_k={bk} must align with group size {group}")
    bk = min(bk, group) if group >= 1 else bk
    for name, dim, blk in (("M", m, bm), ("N", n, bn), ("K", k, bk)):
        if dim % blk:
            raise ValueError(f"{name}={dim} not divisible by block {blk}")

    wn = bn // 2 if bits == 4 else bn
    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, bits=bits, nsteps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, wn), lambda i, j, kk: (kk, j)),
            # one scale row per k block (bk <= group ensures single group)
            pl.BlockSpec((1, bn), lambda i, j, kk, _g=group, _bk=bk: (kk * _bk // _g, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w_q, w_scale)
    return out


__all__ = ["quant_matmul", "pack_int4", "unpack_int4"]
