"""Plain description of a network, independent of the program's graph IR.

A configuration file (`bench/configs/<name>.json`) names its `family`; the
module `bench/models/<family>.py` turns the file's published sizes into a
list of `Block`s (`blocks(cfg)`) and builds the program's own NetSpec
through its normal entry point (`program_netspec(cfg)`). `check_same`
refuses a run whose program graph differs from the plain description in
any shape, stride, activation or bit-width.
"""
from __future__ import annotations

import importlib.util
import itertools
import json
from pathlib import Path
from typing import List, NamedTuple, Optional, Tuple

BENCH = Path(__file__).resolve().parent

CONV, DW, PW, DENSE = "conv", "dw", "pw", "dense"
RELU6, NONE, HSIGMOID = "relu6", "none", "hsigmoid"


class Op(NamedTuple):
    name: str
    kind: str  # conv | dw | pw | dense
    cin: int
    cout: int
    k: int = 1
    stride: int = 1
    act: str = RELU6
    bits: int = 4  # weight bit-width
    act_bits: int = 4  # output activation bit-width


class SE(NamedTuple):
    squeeze: Op  # pooled C -> R, ReLU6
    excite: Op  # R -> C, hard sigmoid gate
    after: str  # name of the op whose output the gate scales


class Block(NamedTuple):
    name: str
    ops: Tuple[Op, ...]
    residual: bool = False
    se: Optional[SE] = None
    avgpool: bool = False


def make_divisible(v: float, divisor: int = 8) -> int:
    """MobileNet channel rounding (Sandler et al. 2018, reference code)."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


def all_ops(blocks: List[Block]):
    for b in blocks:
        yield from b.ops
        if b.se is not None:
            yield b.se.squeeze
            yield b.se.excite


def load_config(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def family(cfg: dict):
    """The module `bench/models/<family>.py` named by the configuration."""
    name = cfg["family"]
    path = BENCH / "models" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no model family file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_models_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_same(blocks: List[Block], net) -> None:
    """Raise unless the program's NetSpec `net` has exactly these blocks."""
    mine = [(b.name, b.residual, b.avgpool,
             [tuple(o) for o in b.ops],
             None if b.se is None else (tuple(b.se.squeeze), tuple(b.se.excite),
                                        b.se.after))
            for b in blocks]

    def op(o):
        return (o.name, o.kind, o.in_ch, o.out_ch, o.kernel, o.stride, o.act,
                o.bits, o.act_bits)

    theirs = [(b.name, b.residual, b.avgpool, [op(o) for o in b.ops],
               None if b.se is None else (op(b.se.squeeze), op(b.se.excite),
                                          b.se_after))
              for b in net.blocks]
    for i, (a, b) in enumerate(itertools.zip_longest(mine, theirs)):
        if a != b:
            raise SystemExit(f"bench: the program's graph differs from the "
                             f"configuration at block {i}: program {b}, "
                             f"configuration {a}")


def input_shape(cfg: dict) -> Tuple[int, int, int]:
    return (cfg["input_hw"], cfg["input_hw"], cfg["input_channels"])


def walk(blocks: List[Block], hw: int):
    """Yield (block, op, h_in, h_out) in execution order; DENSE ops see 1x1."""
    h = hw
    for b in blocks:
        for o in b.ops:
            if o.kind == DENSE:
                yield b, o, 1, 1
                continue
            h2 = -(-h // o.stride)  # SAME padding
            yield b, o, h, h2
            h = h2
        if b.avgpool:
            h = 1
