"""The deployment a cell serves, made from a seed (the configuration's
`weights.seed`): weights, calibration and integer tables.

One jitted program, on the device, draws every float weight and bias from
the seed, runs the float network (float32, `Precision.HIGHEST`) over the
calibration images, observes the ranges the integer tables need, and
quantizes the weights (symmetric, per output channel). The host then
derives the activation quantizers and epilogue constants (DeepDive
arXiv:2007.09490 Sec. 3: ReLU6 fused into the clip, S = 6 / (2^BW - 1),
zero point 0; hard-sigmoid gate S = 1 / (2^BW - 1); linear outputs and
skip-adds asymmetric over the calibrated range).

The plain reference (`reference.py`) runs these tables; `to_program_qnet`
hands the same numbers to the program in its own deployment format.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

from net import CONV, DENSE, DW, HSIGMOID, RELU6, Block, Op, all_ops, input_shape


@dataclasses.dataclass
class Deployment:
    cfg: dict
    blocks: List[Block]
    tables: Dict[str, dict]  # op name -> w_q, w_scale, bias_q, scales, zps, mult
    res_q: Dict[str, Tuple[float, float]]  # residual block -> (scale, zp)


def _weight_shape(op: Op):
    if op.kind == CONV:
        return (op.k, op.k, op.cin, op.cout)
    if op.kind == DW:
        return (op.k, op.k, 1, op.cout)
    return (op.cin, op.cout)


def _fan_in(op: Op) -> int:
    return {CONV: op.k * op.k * op.cin, DW: op.k * op.k}.get(op.kind, op.cin)


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words of key data from any whole-number seed."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def device_program(cfg: dict, blocks: List[Block]):
    """(key words) -> ({op: (int8 weights, weight scales, float biases)},
    {tensor: observed (min, max)})."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hp = lax.Precision.HIGHEST
    ops = list(all_ops(blocks))
    cal = cfg["calibration"]
    n_cal = cal["batches"] * cal["images_per_batch"]
    bias_std = cfg["weights"]["bias_std"]

    def conv(x, w, stride, groups=1):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=hp)

    def apply(x, op, p):
        w, b = p
        if op.kind == CONV:
            y = conv(x, w, op.stride)
        elif op.kind == DW:
            y = conv(x, w, op.stride, groups=op.cin)
        else:
            y = jnp.einsum("...c,cd->...d", x, w, precision=hp)
        y = y + b
        if op.act == RELU6:
            return jnp.clip(y, 0.0, 6.0)
        if op.act == HSIGMOID:
            return jnp.clip(y + 3.0, 0.0, 6.0) / 6.0
        return y

    sizes = [n for op in ops for n in (math.prod(_weight_shape(op)), op.cout)]

    def program(words):
        k_w, k_x = jax.random.split(jax.random.wrap_key_data(words))
        flat = jax.random.normal(k_w, (sum(sizes),))
        params, at = {}, 0
        for op in ops:  # every weight and bias a slice of one normal draw
            n = math.prod(_weight_shape(op))
            w = flat[at:at + n].reshape(_weight_shape(op))
            b = flat[at + n:at + n + op.cout]
            params[op.name] = (math.sqrt(2.0 / _fan_in(op)) * w, bias_std * b)
            at += n + op.cout
        x = jax.random.uniform(k_x, (n_cal, *input_shape(cfg)),
                               minval=cfg["input_range"][0],
                               maxval=cfg["input_range"][1])
        ranges = {}
        for b in blocks:
            y = x
            for op in b.ops:
                y = apply(y, op, params[op.name])
                if op.act == "none":
                    ranges[op.name] = (y.min(), y.max())
                if b.se is not None and b.se.after == op.name:
                    s = jnp.mean(y, axis=(1, 2))
                    s = apply(s, b.se.squeeze, params[b.se.squeeze.name])
                    s = apply(s, b.se.excite, params[b.se.excite.name])
                    y = y * s[:, None, None, :]
            if b.residual:
                y = x + y
                ranges[b.name + "/residual"] = (y.min(), y.max())
            if b.avgpool:
                y = jnp.mean(y, axis=(1, 2))
            x = y
        quant = {}
        for op in ops:
            w, b = params[op.name]
            qmax = 2 ** (op.bits - 1) - 1
            amax = jnp.abs(w).reshape(-1, op.cout).max(axis=0)
            scale = jnp.where(amax > 0, amax / qmax, 1.0)
            w_q = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)
            quant[op.name] = (w_q, scale, b)
        return quant, ranges

    return jax.jit(program)


def _act_qparams(lo: float, hi: float, bits: int) -> Tuple[float, float]:
    """Asymmetric x = S (x_q + z) over [min(lo, 0), max(hi, 0)]."""
    lo, hi = min(lo, 0.0), max(hi, 0.0)
    s = (hi - lo) / (2 ** bits - 1)
    s = s if s > 0 else 1.0
    return s, float(np.round(lo / s))


def _out_qparams(op: Op, ranges) -> Tuple[float, float]:
    qmax = 2 ** op.act_bits - 1
    if op.act == RELU6:
        return 6.0 / qmax, 0.0
    if op.act == HSIGMOID:
        return 1.0 / qmax, 0.0
    return _act_qparams(*ranges[op.name], op.act_bits)


def build(cfg: dict, blocks: List[Block], seed: int) -> Deployment:
    import jax

    quant, ranges = jax.device_get(device_program(cfg, blocks)(seed_words(seed)))
    ranges = {k: (float(a), float(b)) for k, (a, b) in ranges.items()}
    tables: Dict[str, dict] = {}
    res_q: Dict[str, Tuple[float, float]] = {}

    def table(op: Op, in_q, out_q):
        w_q, w_scale, b = quant[op.name]
        w_q = np.asarray(w_q, np.int8)
        if op.kind == DW:
            w_q = w_q.reshape(op.k, op.k, op.cout)
        w_scale = np.asarray(w_scale, np.float32)
        tables[op.name] = dict(
            w_q=w_q, w_scale=w_scale,
            bias_q=np.round(np.asarray(b, np.float64) / out_q[0]
                            - out_q[1]).astype(np.int32),
            in_scale=in_q[0], in_zp=in_q[1], out_scale=out_q[0], out_zp=out_q[1],
            mult=in_q[0] * w_scale.astype(np.float64) / out_q[0])
        return out_q

    lo, hi = cfg["input_range"]
    cur = _act_qparams(lo, hi, cfg["input_bits"])
    for b in blocks:
        for op in b.ops:
            cur = table(op, cur, _out_qparams(op, ranges))
            if b.se is not None and b.se.after == op.name:
                sq = table(b.se.squeeze, cur, _out_qparams(b.se.squeeze, ranges))
                table(b.se.excite, sq, _out_qparams(b.se.excite, ranges))
        if b.residual:
            res_q[b.name] = _act_qparams(*ranges[b.name + "/residual"],
                                         b.ops[-1].act_bits)
            cur = res_q[b.name]
    return Deployment(cfg, blocks, tables, res_q)


def _mantissa_shift(m: np.ndarray):
    """M ~= mantissa * 2^-shift, mantissa in [2^30, 2^31) (fixed-point mode)."""
    frac, exp = np.frexp(m)
    mant = np.round(frac * 2.0 ** 31).astype(np.int64)
    over = mant == 2 ** 31
    return (np.where(over, mant >> 1, mant),
            (31 - np.where(over, exp + 1, exp)).astype(np.int32))


def to_program_qnet(dep: Deployment, net):
    """The same tables in the program's deployment format (`QNet`)."""
    from repro.core.qnet import QNet, QOp

    specs = {op.name: op for _, op in net.all_ops()}
    ops = {}
    for op in all_ops(dep.blocks):
        t = dep.tables[op.name]
        w_q = t["w_q"]
        if op.kind == DW:
            w_q = w_q.reshape(op.k, op.k, 1, op.cout)
        elif op.kind != DENSE and op.kind != CONV:
            w_q = w_q.reshape(1, 1, op.cin, op.cout)
        mantissa, shift = _mantissa_shift(t["mult"])
        ops[op.name] = QOp(
            spec=specs[op.name], w_q=w_q, w_scale=t["w_scale"],
            wsum=w_q.reshape(-1, op.cout).astype(np.int64).sum(0).astype(np.int32),
            bias_q=t["bias_q"], in_scale=t["in_scale"], in_zp=t["in_zp"],
            out_scale=t["out_scale"], out_zp=t["out_zp"], mult=t["mult"],
            mantissa=mantissa, shift=shift, clip=op.act in (RELU6, HSIGMOID))
    return QNet(net, ops, dict(dep.res_q))
