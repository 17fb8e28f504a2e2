"""Shared helpers for the quantized Pallas kernels.

The epilogue implements the paper's Approximator & Clip unit (Fig. 8):
int32 accumulator -> per-channel requant multiply -> round -> +bias -> clip
to [0, 2^BW - 1] (== fused ReLU6 when the op is ReLU6-activated).

`zcorr` is the folded zero-point correction M * z_x * wsum (a per-channel
constant computed at QNet build time), so the kernel itself never sees the
input zero point.
"""
from __future__ import annotations

import jax.numpy as jnp


def requant_clip(acc, mult, zcorr, bias_q, qmax: int, clip: bool = True):
    """acc:int32[..., C]; mult/zcorr:f32[C]; bias_q:i32[C] -> int8-range int32."""
    y = jnp.round(acc.astype(jnp.float32) * mult + zcorr).astype(jnp.int32)
    y = y + bias_q.astype(jnp.int32)
    if clip:
        y = jnp.clip(y, 0, qmax)
    return y


LANES = 128  # TPU vreg lane width: the last block dim's tiling unit


def lane_block(n: int, cap: int) -> int:
    """Block size for a lane (last) dimension of `n`: the largest multiple
    of LANES that divides `n` and is <= `cap`, else the whole dimension —
    the two block shapes the TPU's (8, 128) tiling accepts."""
    for b in range(cap // LANES * LANES, 0, -LANES):
        if n % b == 0:
            return b
    return n


def largest_divisor(n: int, cap: int) -> int:
    """Largest d <= cap with n % d == 0 (d >= 1)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def same_pad_amount(size: int, kernel: int, stride: int):
    """SAME padding (lo, hi) for one spatial dim."""
    out = -(-size // stride)
    total = max((out - 1) * stride + kernel - size, 0)
    lo = total // 2
    return lo, total - lo, out


__all__ = ["LANES", "lane_block", "largest_divisor", "requant_clip", "round_up",
           "same_pad_amount"]
