"""Roofline-term extraction from compiled dry-run artifacts.

    compute    = HLO_FLOPs(per device)       / peak_FLOP/s(chip)
    memory     = HLO_bytes(per device)       / HBM_bw(chip)
    collective = collective_operand_bytes    / link_bw(chip)

FLOPs/bytes come from `compiled.cost_analysis()` of the SPMD-partitioned
(= per-device) module. Collective bytes are NOT in cost_analysis: we parse
the optimized HLO text, build a symbol table of instruction result shapes,
and sum operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+(\S+)\((.*)$")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def shape_bytes(shape_str: str) -> int:
    """'f32[16,128]{1,0}' or '(f32[2], bf16[4,4])' -> total bytes."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind over the whole module."""
    # pass 1: symbol table  name -> result shape string
    shapes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            shapes[m.group(1)] = m.group(2)
    out = {k: 0 for k in _COLLECTIVES}
    out["n_ops"] = 0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, result_shape, opcode, rest = m.groups()
        kind = next((c for c in _COLLECTIVES if opcode.startswith(c)), None)
        if kind is None:
            continue
        # operands: %ref names inside the call parens (stop at metadata)
        args = rest.split(")", 1)[0]
        operand_names = re.findall(r"%([\w\.\-]+)", args)
        b = sum(shape_bytes(shapes.get(n, "")) for n in operand_names)
        if b == 0:  # fallback: result size (e.g. start/done pairs)
            b = shape_bytes(result_shape)
        out[kind] += b
        out["n_ops"] += 1
    return out


@dataclasses.dataclass
class Roofline:
    flops: float  # per device
    hbm_bytes: float  # per device
    coll_bytes: float  # per device (operand bytes)
    coll_detail: Dict[str, int]
    n_devices: int

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def summary(self) -> Dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "collectives": self.coll_detail,
        }


def from_compiled(compiled, n_devices: int) -> Roofline:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    total_coll = sum(v for k, v in coll.items() if k != "n_ops")
    return Roofline(flops, hbm, total_coll, coll, n_devices)


def model_flops(cfg, shape, n_active_params: int) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference) per the assignment."""
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active_params * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active_params * tokens
    # decode: one token per sequence
    return 2.0 * n_active_params * shape.global_batch


__all__ = [
    "Roofline", "from_compiled", "collective_bytes", "shape_bytes",
    "model_flops", "PEAK_FLOPS", "HBM_BW", "LINK_BW",
]
