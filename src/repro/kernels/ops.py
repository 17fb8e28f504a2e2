"""Public jit'd wrappers around the Pallas kernels.

These adapt QNet metadata (per-channel scales, zero-point corrections) into
the raw kernel signatures, pick interpret mode from the platform (CPU ->
interpret=True; TPU -> compiled; this is the one place that decides — the
raw kernels default to compiled), and expose a float `quantized_linear`
for the LM architectures (weight-only quantization, the paper's Sec. 3.2 math).

Every wrapper accepts either a host `QOp` or a device-resident
`cu.PreparedQOp` — prepared ops reuse their cached jnp constants, so a jitted
stage trace built over a `PreparedQNet` closes over device arrays and never
re-uploads per invocation (the PR-2 'device-cached epilogue constants' path).

Fast-path matrix (which CU op hits which kernel — see README 'Performance'):

    op kind   on TPU (compiled Pallas)         off TPU (compiled XLA)
    -------   ------------------------------   ---------------------------
    PW/DENSE  pointwise_conv.pointwise_conv_q  int_pointwise(_f32) + epilogue
    DW        depthwise_conv.depthwise_conv_q  int_depthwise_shifts + epilogue
    IRB       fused_irb.fused_irb_q (Body CU)  per-op path above
    CONV      (stem only) XLA conv             int_conv2d(_f32) + epilogue
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qnet import QNet
from repro.core import cu as _cu
from repro.core import graph as G
from repro.core.quant import QuantConfig
from repro.kernels import depthwise_conv as _dw
from repro.kernels.common import largest_divisor
from repro.kernels import fused_irb as _irb
from repro.kernels import pointwise_conv as _pw
from repro.kernels import quant_matmul as _qmm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _epilogue_consts(qop) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(mult, zcorr, bias') for the kernel epilogue.

    kernel computes round(acc * mult + zcorr) + bias; z_y is already folded
    into bias_q at QNet build time (see qnet._quantize_op). PreparedQOps
    return their device-cached constants directly.
    """
    if isinstance(qop, _cu.PreparedQOp):
        return qop.mult, qop.zcorr, qop.bias_q
    mult = jnp.asarray(qop.mult, jnp.float32)
    zcorr = jnp.asarray(qop.in_zp * qop.mult * qop.wsum, jnp.float32)
    bias = jnp.asarray(qop.bias_q, jnp.int32)
    return mult, zcorr, bias


def _dw_weight(qop) -> jnp.ndarray:
    if isinstance(qop, _cu.PreparedQOp):
        return qop.w_kern
    w = jnp.asarray(qop.w_q)  # [K, K, 1, C] -> [K, K, C]
    return w.reshape(w.shape[0], w.shape[1], w.shape[-1])


def _mat_weight(qop) -> jnp.ndarray:
    if isinstance(qop, _cu.PreparedQOp):
        return qop.w_kern
    w = jnp.asarray(qop.w_q)
    return w[0, 0] if w.ndim == 4 else w


def run_dw_qop(x_q: jnp.ndarray, qop, interpret: Optional[bool] = None,
               block_h: int = 8):
    """Depthwise QNet op via the row-tiled Pallas kernel."""
    interp = (not on_tpu()) if interpret is None else interpret
    mult, zcorr, bias = _epilogue_consts(qop)
    return _dw.depthwise_conv_q(
        x_q, _dw_weight(qop), mult, zcorr, bias,
        kernel=qop.spec.kernel, stride=qop.spec.stride, qmax=qop.qmax,
        clip=qop.clip, block_h=block_h, interpret=interp,
    )


def _pw_zpc(qop) -> jnp.ndarray:
    if isinstance(qop, _cu.PreparedQOp):
        return qop.zpc
    return jnp.int32(qop.in_zp) * jnp.asarray(qop.wsum, jnp.int32)


def run_pw_qop(x_q: jnp.ndarray, qop, interpret: Optional[bool] = None,
               block_m: int = 128, block_n: int = 128, block_k: int = 128):
    """Pointwise / dense QNet op via the Pallas matmul-CU kernel.

    Bit-exact with `int_pointwise` + `quantized_op_epilogue` (the kernel
    applies the identical integer zero-point correction and f32 requant
    sequence). Clips to [0, qmax] like the reference epilogue — linear ops
    included, since the output quantizer's codomain is [0, qmax] either way.

    `block_m/n/k` expose the kernel's tile sizes (the route autotuner
    sweeps them; tiling only reorders identical integer accumulations, so
    any tile choice stays bit-exact).
    """
    interp = (not on_tpu()) if interpret is None else interpret
    mult = qop.mult if isinstance(qop, _cu.PreparedQOp) else jnp.asarray(
        qop.mult, jnp.float32)
    bias = qop.bias_q if isinstance(qop, _cu.PreparedQOp) else jnp.asarray(
        qop.bias_q, jnp.int32)
    return _pw.pointwise_conv_q(
        x_q, _mat_weight(qop), mult, _pw_zpc(qop), bias,
        qmax=qop.qmax, clip=True, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=interp,
    )


def fusable_irb(block: G.BlockSpec) -> bool:
    """True when `block` fits the fused Body-CU kernel: the canonical
    expand -> dw -> project shape with no squeeze-excitation branch and one
    activation bit-width (the kernel clips all three stages with a single
    qmax, so mixed act_bits would requantize wrongly)."""
    return (
        len(block.ops) == 3
        and block.se is None
        and block.ops[0].kind == G.PW
        and block.ops[1].kind == G.DW
        and block.ops[2].kind == G.PW
        and not block.avgpool
        and len({op.act_bits for op in block.ops}) == 1
    )


def run_irb_block(
    x_q: jnp.ndarray,
    block: G.BlockSpec,
    qnet: QNet,
    in_s: float,
    in_z: float,
    interpret: Optional[bool] = None,
):
    """Body-CU invocation: a full IRB through the fused Pallas kernel.

    Only for expand->dw->project blocks (no SE). Returns (y_q, out_s, out_z).
    """
    interp = (not on_tpu()) if interpret is None else interpret
    assert len(block.ops) == 3 and block.se is None
    q1, q2, q3 = (qnet.ops[op.name] for op in block.ops)
    m1, _, b1 = _epilogue_consts(q1)
    m2, c2, b2 = _epilogue_consts(q2)
    m3, _, b3 = _epilogue_consts(q3)
    res_consts = None
    out_s, out_z = q3.out_scale, q3.out_zp
    if block.residual:
        y_s, y_z = qnet.res_q[block.name]
        # the float arithmetic of cu._residual_add, constant for constant
        res_consts = (in_z, in_s / y_s, q3.out_zp, q3.out_scale / y_s,
                      round(y_z))
        out_s, out_z = y_s, y_z
    y = _irb.fused_irb_q(
        x_q,
        _mat_weight(q1),
        m1, _pw_zpc(q1), b1,
        _dw_weight(q2), m2, c2, b2,
        _mat_weight(q3),
        m3, _pw_zpc(q3), b3,
        kernel=q2.spec.kernel,
        stride=q2.spec.stride,
        qmax=q3.qmax,
        residual=block.residual,
        res_consts=res_consts,
        interpret=interp,
    )
    return y, out_s, out_z


def run_block_kernels(
    x_q: jnp.ndarray,
    block: G.BlockSpec,
    qnet,
    in_s: float,
    in_z: float,
    interpret: Optional[bool] = None,
):
    """One block through the per-op Pallas kernels (no IRB fusion).

    Mirrors `cu.run_block` exactly, but routes DW ops through the row-tiled
    depthwise kernel and PW/DENSE ops through the pointwise-CU kernel — the
    compiled path for Head/Tail/Classifier stages and for Body blocks the
    fused-IRB kernel cannot take (SE branches, mixed act_bits). CONV (the
    stem) and the SE gate stay on the XLA path inside `cu.run_block`'s
    reference op body. Returns (y_q, out_s, out_z).
    """
    y = x_q
    cur_s, cur_z = in_s, in_z
    for op in block.ops:
        qop = qnet.ops[op.name]
        if op.kind == G.DW:
            y = run_dw_qop(y, qop, interpret=interpret)
        elif op.kind in (G.PW, G.DENSE) and op.act != G.HSIGMOID:
            y = run_pw_qop(y, qop, interpret=interpret)
        else:
            y = _cu._run_qop(y, qop, fixed_point=False)
        cur_s, cur_z = qop.out_scale, qop.out_zp
        if block.se is not None and block.se_after == op.name:
            sq = qnet.ops[block.se.squeeze.name]
            ex = qnet.ops[block.se.excite.name]
            pooled = jnp.round(
                jnp.mean(y.astype(jnp.float32), axis=(1, 2))).astype(jnp.int32)
            s = run_pw_qop(pooled, sq, interpret=interpret)
            gate_q = _cu._run_qop(s, ex, fixed_point=False)  # hsigmoid gate
            y = jnp.round(
                y.astype(jnp.float32)
                * gate_q[:, None, None, :].astype(jnp.float32)
                * ex.out_scale
            ).astype(jnp.int32)
    if block.residual:
        y_s, y_z = qnet.res_q[block.name]
        qmax = 2 ** block.ops[-1].act_bits - 1
        y = _cu._residual_add(x_q, in_s, in_z, y, cur_s, cur_z, y_s, y_z, qmax)
        cur_s, cur_z = y_s, y_z
    if block.avgpool:
        y = jnp.round(jnp.mean(y.astype(jnp.float32), axis=(1, 2))).astype(jnp.int32)
    return y, cur_s, cur_z


# ---------------------------------------------------------------------------
# LM-side weight-only quantized linear (per-channel / grouped, BW in {4, 8})
# ---------------------------------------------------------------------------


def quantize_weight_for_matmul(
    w: jnp.ndarray, bits: int = 4, group_size: Optional[int] = None
):
    """[K, N] float -> (w_q packed, scales [G, N]) symmetric per-(group, out)."""
    k, n = w.shape
    if group_size is None:
        group_size = k
    g = k // group_size
    wg = w.reshape(g, group_size, n)
    cfg = QuantConfig(bits, symmetric=True, channel_axis=None)
    amax = jnp.max(jnp.abs(wg), axis=1)  # [G, N]
    scale = jnp.where(amax > 0, amax / cfg.qmax, 1.0)
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), cfg.qmin, cfg.qmax)
    q = q.reshape(k, n).astype(jnp.int32)
    if bits == 4:
        packed = _qmm.pack_int4(jnp.where(q < 0, q + 16, q).astype(jnp.int32))
        return packed, scale
    return q.astype(jnp.int8), scale


def quantized_linear(
    x: jnp.ndarray,
    w_q: jnp.ndarray,
    w_scale: jnp.ndarray,
    bits: int = 4,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """y = x @ dequant(w_q). x: [..., K]. Uses the Pallas quant_matmul."""
    interp = (not on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # pad M to a block multiple
    bm = 128 if m >= 128 else m
    pad = (-m) % bm
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    n = w_q.shape[1] * (2 if bits == 4 else 1)
    # largest divisor of N at most 128 (one giant N block would blow VMEM
    # for non-multiple-of-128 N; any divisor tiles exactly)
    bn = largest_divisor(n, 128)
    group = k // w_scale.shape[0]
    bk = min(512, group) if group < 512 or group % 512 else 512
    # bk must divide K and align with the scale-group size; halving can
    # bottom out (e.g. group == 0 when there are more scale rows than K, or
    # no shared power-of-two factor) — fall back to gcd(k, group), floor 1
    while bk > 1 and (k % bk or (group % bk and bk % group)):
        bk //= 2
    if bk < 1 or k % bk or (group % bk and bk % group):
        bk = max(math.gcd(k, group), 1)
    y = _qmm.quant_matmul(
        x2, w_q, w_scale, bits=bits, block_m=bm, block_n=bn, block_k=bk,
        interpret=interp,
    )
    if pad:
        y = y[:m]
    return y.reshape(*lead, n).astype(x.dtype)


def decode_attend(q, kv_cache, kv_len, interpret: Optional[bool] = None):
    """Flash-decode attention over a model KV cache dict.

    q: [B, 1, H, dh] (one new token); kv_cache: {"k","v"[,"k_scale","v_scale"]}
    with k/v [B, S, KV, dh]. Returns [B, 1, H, dh].
    """
    from repro.kernels.decode_attention import decode_attention

    interp = (not on_tpu()) if interpret is None else interpret
    b, one, h, dh = q.shape
    kv = kv_cache["k"].shape[2]
    qg = q.reshape(b, kv, h // kv, dh)
    out = decode_attention(
        qg, kv_cache["k"], kv_cache["v"], kv_len,
        kv_cache.get("k_scale"), kv_cache.get("v_scale"), interpret=interp)
    return out.reshape(b, 1, h, dh)


__all__ = [
    "run_dw_qop",
    "run_pw_qop",
    "run_block_kernels",
    "fusable_irb",
    "run_irb_block",
    "quantize_weight_for_matmul",
    "quantized_linear",
    "decode_attend",
    "on_tpu",
]
