"""One general load generator, driven by a traffic file
(`bench/traffic/<name>.json`).

Keys of a traffic file:

- `loop`: "closed" (a client that sends `outstanding` frames, waits for the
  drain that answers them, and sends again) or "open" (frames arrive on a
  schedule whatever the server does);
- `buckets`: the engine's micro-batch sizes (warmed up in set-up);
- `pool`: how many distinct images the seed makes; request k sends image
  k mod pool (the engine caches nothing by content);
- open loop: `rate_per_s` (frames per second), `fps_per_camera`,
  `cameras_per_recorder` (the frames of one recorder arrive together) and
  `jitter_ms` (per recorder frame group, uniform in +-jitter).

Open-loop recorders are spread evenly over one frame interval and the seed
only permutes which recorder takes which phase and draws the jitter, so
every seed offers the same load in another order.

Every request is submitted with `now=` its scheduled time, and its latency
runs from that time until `run()` hands back its answer. No request has a
deadline and the engine's queue is unbounded (`bench/run.py`): a frame that
waits is late, never refused.
"""
from __future__ import annotations

import dataclasses
import json
import random
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
LEAD_S = 0.02  # the open loop's schedule starts this far after the window opens


def load(name: str) -> Dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic file {path}")
    with open(path) as f:
        traffic = json.load(f)
    if traffic["loop"] not in ("closed", "open"):
        raise SystemExit(f"bench: {path}: loop must be closed or open")
    return traffic


def open_schedule(traffic: Dict, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets (s), sorted, of every frame due in [0, seconds)."""
    fps, per = traffic["fps_per_camera"], traffic["cameras_per_recorder"]
    recorders = traffic["rate_per_s"] / (fps * per)
    if recorders != int(recorders) or recorders < 1:
        raise SystemExit(f"bench: rate_per_s {traffic['rate_per_s']} is not a "
                         f"whole number of recorders of {per} cameras at {fps} fps")
    recorders = int(recorders)
    rng = np.random.default_rng([seed, 1])
    phase = (rng.permutation(recorders) + 0.5) / (recorders * fps)
    frames = int(np.ceil(seconds * fps)) + 1
    t = phase[:, None] + np.arange(frames)[None, :] / fps
    t = t + rng.uniform(-1, 1, t.shape) * traffic["jitter_ms"] * 1e-3
    t = np.sort(t[(t >= 0) & (t < seconds)])
    return np.repeat(t, per)


@dataclasses.dataclass
class Window:
    start: float = 0.0  # clock time the window opened (first request due)
    end: float = 0.0  # clock time the last answer due in it came back
    scheduled: List[float] = dataclasses.field(default_factory=list)  # per request
    submitted: List[float] = dataclasses.field(default_factory=list)  # sent at
    returned: List[float] = dataclasses.field(default_factory=list)  # answered at
    ok: List[bool] = dataclasses.field(default_factory=list)  # answered "ok"
    image: List[int] = dataclasses.field(default_factory=list)  # pool index
    sample: List[Tuple[int, np.ndarray]] = dataclasses.field(default_factory=list)
    backlog: List[int] = dataclasses.field(default_factory=list)  # at drain start


class _Client:
    """Sends requests and keeps per request only plain numbers, plus the
    logits of a uniform sample of the ok answers (reservoir sampling, seeded)
    for the correctness check: holding every answer would grow the heap the
    garbage collector walks, and its pauses would land in the window."""

    def __init__(self, router, model, pool, clock, annotate, sample: int, seed: int):
        self.router, self.model, self.pool = router, model, pool
        self.clock, self.annotate = clock, annotate
        self.w = Window()
        self._handles: Dict[Tuple[str, int], int] = {}
        self._size, self._rng, self._seen = sample, random.Random(seed), 0

    def submit(self, due: float) -> None:
        k = len(self.w.scheduled)
        h = self.router.submit(self.model, self.pool[k % len(self.pool)], now=due)
        self._handles[h] = k
        self.w.scheduled.append(due)
        self.w.submitted.append(self.clock())
        self.w.returned.append(float("nan"))
        self.w.ok.append(False)
        self.w.image.append(k % len(self.pool))

    def drain(self) -> None:
        self.w.backlog.append(len(self._handles))
        with self.annotate("bench.drain"):
            results = self.router.run()
        t = self.clock()
        for h, res in results.items():
            k = self._handles.pop(h)
            self.w.returned[k] = t
            if res.status == "ok":
                self.w.ok[k] = True
                self._keep(k, res.logits)

    def _keep(self, k: int, logits) -> None:
        self._seen += 1
        if len(self.w.sample) < self._size:
            self.w.sample.append((k, logits))
        else:
            j = self._rng.randrange(self._seen)
            if j < self._size:
                self.w.sample[j] = (k, logits)


def drive(router, model: str, pool: np.ndarray, traffic: Dict, seconds: float,
          seed: int, sample: int, between: Callable[[float], None],
          annotate, clock=time.perf_counter) -> Window:
    """Offer the traffic for `seconds`; `between(elapsed)` runs after every
    drain (the harness starts and stops the profiler there). `sample` ok
    answers, drawn uniformly with the seed, keep their logits."""
    c = _Client(router, model, pool, clock, annotate, sample, seed)
    start = clock()
    c.w.start = start
    if traffic["loop"] == "closed":
        while clock() - start < seconds:
            with annotate("bench.submit"):
                due = clock()
                for _ in range(traffic["outstanding"]):
                    c.submit(due)
            c.drain()
            between(clock() - start)
    else:
        sched = start + LEAD_S + open_schedule(traffic, seconds, seed)
        c.w.start = sched[0]
        i = 0
        while i < len(sched):
            now = clock()
            if sched[i] > now:
                with annotate("bench.wait_arrival"):
                    time.sleep(sched[i] - now)
                now = clock()
            with annotate("bench.submit"):
                while i < len(sched) and sched[i] <= now:
                    c.submit(float(sched[i]))
                    i += 1
            c.drain()
            between(clock() - start)
    c.w.end = clock()
    return c.w
