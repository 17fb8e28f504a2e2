"""Plain integer reference of a quantized DSCNN, in NumPy on the host.

It imports nothing of the program. It runs the deployment tables that
`deploy.py` makes from the seed (integer weights, scales, zero points,
biases), with the number system of DeepDive's integer datapath
(arXiv:2007.09490 Sec. 3-4):

    x = S_x (x_q + z_x),  w = S_w[c] w_q,  acc = sum x_q w_q  (exact integers)
    y_q = clip(round(f32(acc + z_x wsum[c]) * f32(M[c])) + b_q[c], 0, 2^BW - 1)
    M[c] = S_x S_w[c] / S_y,  b_q = round(b / S_y - z_y)

Every float step is one float32 operation, rounded on its own, in the order
written here. Integers are held in float32; an accumulation runs in float32
where every partial sum stays below 2^24 (exact there), else in float64.
`low=True` is the control: each float step is rounded to bfloat16 (the nearest precision below the float32 the configuration
states), everything else unchanged.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import ml_dtypes
import numpy as np

from net import CONV, DENSE, DW, HSIGMOID, PW, Block, Op

F = np.float32
CHUNK = 8  # images per pass, so activations of one pass stay small
THREADS = 4  # passes in flight (NumPy releases the GIL in its array loops)


class _Num:
    """Float steps in float32, or in bfloat16 for the control."""

    def __init__(self, low: bool):
        self.low = low

    def r(self, x):
        x = np.asarray(x, F)
        return x.astype(ml_dtypes.bfloat16).astype(F) if self.low else x


def _same_pad(size: int, k: int, stride: int):
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2, out


def _accumulate(x: np.ndarray, op: Op, w: np.ndarray) -> np.ndarray:
    """Exact integer accumulator of one op, in the float type of `w`."""
    if op.kind in (PW, DENSE):
        return x @ w
    n, h, wd, c = x.shape
    lo, hi, ho = _same_pad(h, op.k, op.stride)
    lo_w, hi_w, wo = _same_pad(wd, op.k, op.stride)
    xp = np.pad(x, ((0, 0), (lo, hi), (lo_w, hi_w), (0, 0)))
    s = op.stride

    def tap(i, j):
        return xp[:, i:i + (ho - 1) * s + 1:s, j:j + (wo - 1) * s + 1:s, :]

    if op.kind == DW:
        acc = np.zeros((n, ho, wo, c), w.dtype)
        for i in range(op.k):
            for j in range(op.k):
                acc += tap(i, j) * w[i, j]
        return acc
    if op.kind == CONV:
        cols = np.concatenate([tap(i, j) for i in range(op.k)
                               for j in range(op.k)], axis=-1)
        return cols @ w.reshape(-1, w.shape[-1])
    raise ValueError(op.kind)


def _op(x, op: Op, t: Dict, num: _Num) -> np.ndarray:
    w2 = t["w_q"].reshape(-1, t["w_q"].shape[-1]).astype(np.int64)
    wsum, z_x = w2.sum(0), int(t["in_zp"])
    bound = (int(x.max()) + abs(z_x)) * int(np.abs(w2).sum(0).max())
    acc = _accumulate(x, op, t["w_q"].astype(F if bound < 2 ** 24 else np.float64))
    qmax = 2 ** op.act_bits - 1
    if op.act == HSIGMOID:
        zterm = num.r(F(t["in_zp"]) * wsum.astype(F))
        y = num.r(num.r(acc.astype(F) + zterm)
                  * num.r(F(t["in_scale"]) * t["w_scale"].astype(F)))
        y = num.r(y + num.r(t["bias_q"].astype(F) * F(t["out_scale"])))
        gate6 = np.clip(num.r(y + F(3.0)), 0, 6)
        return np.round(num.r(gate6 * num.r(F(1.0 / (6.0 * t["out_scale"])))))
    corrected = (acc + (z_x * wsum).astype(acc.dtype)).astype(F)
    y = np.round(num.r(corrected * num.r(t["mult"].astype(F))))
    return np.clip(y + t["bias_q"].astype(F), 0, qmax)


def _mean(y: np.ndarray, num: _Num) -> np.ndarray:
    """round(mean) over the spatial axes, the mean as one float32 divide
    of an exact integer sum."""
    n = y.shape[1] * y.shape[2]
    return np.round(num.r(y.sum(axis=(1, 2), dtype=np.float64).astype(F) / F(n)))


def _block(x, b: Block, tables, res_q, num: _Num):
    y = x
    for op in b.ops:
        t = tables[op.name]
        y = _op(y, op, t, num)
        if b.se is not None and b.se.after == op.name:
            s = _op(_mean(y, num), b.se.squeeze, tables[b.se.squeeze.name], num)
            gate = _op(s, b.se.excite, tables[b.se.excite.name], num)
            scale = F(tables[b.se.excite.name]["out_scale"])
            y = np.round(num.r(num.r(y.astype(F) * gate[:, None, None, :].astype(F))
                               * num.r(scale)))
    if b.residual:
        first, last = tables[b.ops[0].name], tables[b.ops[-1].name]
        y_s, y_z = res_q[b.name]
        qmax = 2 ** b.ops[-1].act_bits - 1
        a = num.r(num.r(x.astype(F) + F(first["in_zp"]))
                  * num.r(F(first["in_scale"] / y_s)))
        c = num.r(num.r(y.astype(F) + F(last["out_zp"]))
                  * num.r(F(last["out_scale"] / y_s)))
        y = np.clip(np.round(num.r(a + c)) - F(round(y_z)), 0, qmax)
    if b.avgpool:
        y = _mean(y, num)
    return y


def logits(deployment, images: np.ndarray, low: bool = False) -> np.ndarray:
    """Float32 logits [N, classes] of float images [N, H, W, C]."""
    num = _Num(low)
    blocks: List[Block] = deployment.blocks
    tables, res_q = deployment.tables, deployment.res_q
    first = tables[blocks[0].ops[0].name]
    last = tables[blocks[-1].ops[-1].name]
    hi = 2 ** deployment.cfg["input_bits"] - 1

    def one_pass(x):
        q = np.round(num.r(num.r(np.asarray(x, F) / num.r(F(first["in_scale"])))
                           - F(first["in_zp"])))
        y = np.clip(q, 0, hi)
        for b in blocks:
            y = _block(y, b, tables, res_q, num)
        return num.r(num.r(y + F(last["out_zp"])) * F(last["out_scale"]))

    chunks = [images[i:i + CHUNK] for i in range(0, len(images), CHUNK)]
    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(one_pass, chunks)))
