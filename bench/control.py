#!/usr/bin/env python3
"""The control of a cell's correctness check, read on the chip.

    python3 bench/control.py --workload <name> --seeds 11,12,13

For each seed it makes the cell's deployment and image pool exactly as a
run does, draws as many answers as a run compares, and counts the logits on
which the control (the plain reference with its float steps in bfloat16,
standing in the program's place) differs from the reference. A sound
program reads 0 (`mismatched_logits`, limit 0); the control has to read
more. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import numpy as np  # noqa: E402

import deploy  # noqa: E402
import net as netlib  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def reading(cell: run.Cell, seed: int) -> dict:
    blocks = netlib.family(cell.cfg).blocks(cell.cfg)
    dep = deploy.build(cell.cfg, blocks, cell.cfg["weights"]["seed"])
    rng = np.random.default_rng([seed, 2])
    pool = rng.uniform(-1, 1, (cell.traffic["pool"], *netlib.input_shape(cell.cfg))
                       ).astype(np.float32)
    picks = np.random.default_rng([seed, 3]).integers(0, len(pool), run.SAMPLE)
    images = sorted(set(picks.tolist()))
    t = time.perf_counter()
    ref = dict(zip(images, reference.logits(dep, pool[images])))
    low = dict(zip(images, reference.logits(dep, pool[images], low=True)))
    diff = [int(np.count_nonzero(ref[i] != low[i])) for i in picks]
    return {"seed": seed, "compared": len(picks), "images": len(images),
            "control_mismatched_logits": sum(diff),
            "control_answers_differing": sum(d > 0 for d in diff),
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    with open(run.ROOT / "BENCHMARK.json") as f:
        cell = run.resolve(json.load(f), args.workload)
    run.require_chips(cell.chips)
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, **reading(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
