"""Operations and bytes the algorithm needs, from the plain network
description alone (independent of how any kernel tiles or pads it).

Per op: 2 operations per multiply-accumulate; bytes are the input and
output activations at 1 byte per element, the weights at 1 byte per
element, and 12 bytes per output channel of epilogue constants (multiplier,
zero-point correction, bias). A fused inverted-residual block moves only
its own input and output; its expanded intermediate never leaves the
kernel. Padding, wider activation types and halo re-reads are therefore
not work: they show as a lower share of the roofline.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Tuple

from net import DENSE, DW, PW, Block, Op, walk

EPILOGUE_BYTES = 12  # f32 multiplier + i32 zero-point correction + i32 bias


def op_macs(op: Op, h_in: int, h_out: int) -> int:
    if op.kind == DENSE:
        return op.cin * op.cout
    if op.kind == DW:
        return h_out * h_out * op.k * op.k * op.cout
    return h_out * h_out * op.k * op.k * op.cin * op.cout  # conv, pw (k = 1)


def weight_elems(op: Op) -> int:
    return op.k * op.k * (1 if op.kind == DW else op.cin) * op.cout


def op_work(op: Op, h_in: int, h_out: int, rows: int) -> Tuple[int, int]:
    """(operations, bytes) of one op over `rows` images."""
    area_in, area_out = (1, 1) if op.kind == DENSE else (h_in * h_in, h_out * h_out)
    acts = rows * (area_in * op.cin + area_out * op.cout)
    return (2 * rows * op_macs(op, h_in, h_out),
            acts + weight_elems(op) + EPILOGUE_BYTES * op.cout)


def fused_block_work(ops: List[Tuple[Op, int, int]], rows: int) -> Tuple[int, int]:
    """(operations, bytes) of one block run as one kernel: `ops` holds
    (op, h_in, h_out) in order; only the block's input and output move."""
    first, h0, _ = ops[0]
    last, _, h1 = ops[-1]
    n_ops = sum(2 * rows * op_macs(o, a, b) for o, a, b in ops)
    n_bytes = rows * (h0 * h0 * first.cin + h1 * h1 * last.cout)
    n_bytes += sum(weight_elems(o) + EPILOGUE_BYTES * o.cout for o, _, _ in ops)
    return n_ops, n_bytes


def least_seconds(ops: int, nbytes: int, peak: Dict) -> float:
    """The least time the chip could take: compute or memory bound."""
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def macs_per_image(blocks: List[Block], hw: int) -> int:
    total = sum(op_macs(o, a, b) for _, o, a, b in walk(blocks, hw))
    return total + sum(op_macs(b.se.squeeze, 1, 1) + op_macs(b.se.excite, 1, 1)
                       for b in blocks if b.se is not None)


def fusable(block: Block) -> bool:
    """Expand -> depthwise -> project with no squeeze-excitation and one
    activation width: the shape a fused inverted-residual kernel takes."""
    kinds = [o.kind for o in block.ops]
    return (kinds == [PW, DW, PW] and block.se is None and not block.avgpool
            and len({o.act_bits for o in block.ops}) == 1)


def block_ops(blocks: List[Block], hw: int) -> Dict[str, List[Tuple[Op, int, int]]]:
    """Block name -> its (op, h_in, h_out) in execution order."""
    out: Dict[str, List[Tuple[Op, int, int]]] = {}
    for b, o, a, c in walk(blocks, hw):
        out.setdefault(b.name, []).append((o, a, c))
    return out


def load_peak(device_kind: str) -> Dict:
    with open(Path(__file__).resolve().parent / "peaks.json") as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} in "
                         f"peaks.json ({sorted(peaks)})")
    return peaks[device_kind]

