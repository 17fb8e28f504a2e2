"""Lay the program's own spans on a profiled slice, and name its device time.

    python3 bench/host_spans.py TRACE_DIR SPANS.json

TRACE_DIR is what `bench/run.py --trace 1 --keep-trace TRACE_DIR` kept (the
profiler's `*.xplane.pb` and `notes.json`: the slice and the benchmark's
`bench.*` notes on the trace's clock); SPANS.json is the program's span
trace of the same run (`repro.obs.Tracer.save`), whose
`otherData.origin_unix_ns` puts its spans on the wall clock the profiler
session is stamped with (`profile_start_time`). It prints on stderr:

- the slice's idle seconds per label, each gap split at note boundaries
  and named by the innermost note (`trace_reduce.label`): the program's
  spans as `serve.<span>` beside the `bench.*` notes, so a gap inside a
  drain is named after the host step it fell in (`serve.drain` is router
  glue between steps);
- device seconds per stage program (`jit_stage_<cu>` on the device plane's
  `XLA Modules` line), beside the slice's busy seconds;
- how the two clocks agree: each stage execution against its micro-batch's
  `dispatch:<cu>` span, each `harvest` against its batch's Classifier
  execution, and the range of constant offsets between the profiler's
  device timestamps and the host's wall clock that keeps every execution
  after its dispatch and every harvest after its execution (spans and
  notes move by the middle of that range before they name idle time);
- over the whole window, the longest per-batch span, the longest host
  interval between two consecutive per-batch spans of a drain, and the
  longest interval between drains.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402

MODULES_LINE = "XLA Modules"
PREFIX = "serve."
_STAGE = re.compile(r"^jit_stage_([A-Za-z0-9]+)\b")

Event = trace_reduce.Event  # (name, start_ns, end_ns)


def spans(doc: Dict) -> List[Dict]:
    """The program's finished spans ("X" events) of a span trace."""
    return [ev for ev in doc["traceEvents"] if ev.get("ph") == "X"]


def on_profile_clock(doc: Dict, t0_ns: int) -> List[Tuple[Dict, float, float]]:
    """Each span with its start and end in ns after the profile's start;
    empty for a trace without a wall-clock origin (an injected clock)."""
    origin = doc.get("otherData", {}).get("origin_unix_ns")
    if origin is None:
        return []
    base = origin - t0_ns
    return [(ev, base + ev["ts"] * 1e3, base + (ev["ts"] + ev["dur"]) * 1e3)
            for ev in spans(doc)]


def serve_notes(doc: Dict, t0_ns: int, window: Tuple[float, float],
                shift_ns: float = 0.0) -> List[Event]:
    """The program's spans that overlap the slice, as notes `serve.<span>`
    on the trace's clock, for `trace_reduce.reduce` and `idle_by_label`;
    `shift_ns` moves them onto the device's clock (`clock_agreement`)."""
    lo, hi = window
    return [(PREFIX + ev["name"], s + shift_ns, e + shift_ns)
            for ev, s, e in on_profile_clock(doc, t0_ns)
            if e + shift_ns > lo and s + shift_ns < hi]


class _Notes:
    """Notes sorted by start, for the ones that overlap an interval."""

    def __init__(self, notes: List[Event]):
        self.notes = sorted(notes, key=lambda n: n[1])
        self.starts = [n[1] for n in self.notes]
        self.reach = max((e - s for _, s, e in self.notes), default=0.0)

    def near(self, lo: float, hi: float) -> List[Event]:
        i = bisect.bisect_left(self.starts, lo - self.reach)
        j = bisect.bisect_right(self.starts, hi)
        return [n for n in self.notes[i:j] if n[2] >= lo]


def idle_gaps(ops: List[Event], window: Tuple[float, float],
              notes: List[Event]) -> List[Tuple[str, float, float]]:
    """The gaps in the union of the device's ops inside the slice, split at
    the notes' boundaries: (label, start, end), each piece named by the
    innermost note over its midpoint (`trace_reduce.label`)."""
    lo, hi = window
    merged = trace_reduce.union([(s, e) for _, s, e in ops], lo, hi)
    index, out = _Notes(notes), []
    for s, e in trace_reduce.gaps(merged, lo, hi):
        near = index.near(s, e)
        cuts = sorted({s, e} | {t for _, a, b in near for t in (a, b) if s < t < e})
        out += [(trace_reduce.label((a + b) / 2, near), a, b)
                for a, b in zip(cuts, cuts[1:])]
    return out


def idle_by_label(ops: List[Event], window: Tuple[float, float],
                  notes: List[Event]) -> Dict[str, float]:
    """Idle seconds of the slice per label (`idle_gaps`); the values sum to
    the slice's idle time."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in idle_gaps(ops, window, notes):
        out[name] += (e - s) * 1e-9
    return dict(out)


def stage_executions(profile) -> Dict[str, List[Event]]:
    """Stage name (`head`, `body`, ...) -> its program's executions on the
    device planes' `XLA Modules` lines, in start order."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for name, s, e in trace_reduce._events(line):
                        m = _STAGE.match(name)
                        if m:
                            out[m.group(1)].append((name, s, e))
    return {k: sorted(v, key=lambda ev: ev[1]) for k, v in out.items()}


def stage_seconds(executions: Dict[str, List[Event]],
                  window: Tuple[float, float]) -> Dict[str, float]:
    """Device seconds per stage program inside the slice."""
    lo, hi = window
    return {cu: sum(max(0.0, min(e, hi) - max(s, lo)) for _, s, e in evs) * 1e-9
            for cu, evs in executions.items()}


def clock_agreement(doc: Dict, t0_ns: int, executions: Dict[str, List[Event]],
                    window: Tuple[float, float], classifier: str = "classifier"
                    ) -> Dict[str, object]:
    """Pair each stage execution in the slice with the `dispatch:<cu>` span
    of the same place in dispatch order, and each `harvest` with its batch's
    Classifier execution, on the clocks as recorded. In us:
    `exec_minus_dispatch_us` (execution start less dispatch start; below 0,
    the device seems to start before the host asked) and
    `harvest_minus_exec_us` (harvest end less Classifier end), each (min,
    max); `offset_us`, the range of device-clock-less-host-clock offsets
    that keeps every execution after its dispatch and every harvest after
    its execution (empty, lo > hi, if none does). `pairs:<cu>` is None
    where a stage's counts differ."""
    lo, hi = window
    placed = [(ev, s, e) for ev, s, e in on_profile_clock(doc, t0_ns) if lo <= s and e <= hi]
    out: Dict[str, object] = {}
    lead, by_batch = [], {}
    for cu, evs in executions.items():
        runs = [ev for ev in evs if lo <= ev[1] and ev[2] <= hi]
        disp = sorted((x for x in placed if x[0]["name"] == f"dispatch:{cu}"),
                      key=lambda x: x[1])
        if len(runs) != len(disp):
            out[f"pairs:{cu}"] = None
            continue
        out[f"pairs:{cu}"] = len(runs)
        lead += [(r[1] - d[1]) * 1e-3 for r, d in zip(runs, disp)]
        if cu == classifier:
            by_batch = {d[0]["args"].get("batch"): r for r, d in zip(runs, disp)}
    tail = [(e - by_batch[ev["args"].get("batch")][2]) * 1e-3
            for ev, s, e in placed
            if ev["name"] == "harvest" and ev.get("args", {}).get("batch") in by_batch]
    out["exec_minus_dispatch_us"] = (min(lead), max(lead)) if lead else None
    out["harvest_minus_exec_us"] = (min(tail), max(tail)) if tail else None
    out["harvests_paired"] = len(tail)
    out["offset_us"] = (-min(tail), min(lead)) if lead and tail else None
    return out


def longest(doc: Dict) -> Dict[str, Optional[Tuple[str, float]]]:
    """Over the whole trace, each as (name, seconds): the longest per-batch
    span (one with a `batch` arg), the longest host interval between two
    consecutive per-batch spans of one drain (`<before> -> <after>`), and
    the longest interval between two drains (the client's own time)."""
    xs = spans(doc)
    drains = sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in xs if ev["name"] == "drain")
    batch = sorted((ev for ev in xs if "batch" in ev.get("args", {})),
                   key=lambda ev: ev["ts"])
    out: Dict[str, Optional[Tuple[str, float]]] = {
        "span": max(((ev["name"], ev["dur"] * 1e-6) for ev in batch),
                    key=lambda x: x[1], default=None),
        "between_drains": max(((f"drain {i} -> {i + 1}", (b[0] - a[1]) * 1e-6)
                               for i, (a, b) in enumerate(zip(drains, drains[1:]))),
                              key=lambda x: x[1], default=None),
        "in_drain": None}

    starts = [a for a, _ in drains]

    def drain_of(t):
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t <= drains[i][1] else None

    end, before = None, None
    for ev in batch:
        here = drain_of(ev["ts"])
        if end is not None and here is not None and here == drain_of(end):
            gap = (ev["ts"] - end) * 1e-6
            if out["in_drain"] is None or gap > out["in_drain"][1]:
                out["in_drain"] = (f"{before} -> {ev['name']}", gap)
        if end is None or ev["ts"] + ev["dur"] >= end:
            end, before = ev["ts"] + ev["dur"], ev["name"]
    return out


def main(trace_dir: str, spans_path: str) -> None:
    profile = trace_reduce.load(str(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]))
    t0 = trace_reduce.profile_start_ns(profile)
    with open(Path(trace_dir) / "notes.json") as f:
        kept = json.load(f)
    with open(spans_path) as f:
        doc = json.load(f)
    window = tuple(kept["window"])
    execs = stage_executions(profile)
    clocks = clock_agreement(doc, t0, execs, window)
    print(f"host_spans: clocks as recorded {clocks}", file=sys.stderr)
    # the profiler's device timestamps sit a constant offset off the host's
    # wall clock; host spans and notes move by the middle of its range
    shift = 1e3 * sum(clocks["offset_us"]) / 2 if clocks["offset_us"] else 0.0
    notes = [(n, a + shift, b + shift) for n, a, b in kept["notes"]] + serve_notes(
        doc, t0, window, shift)
    ops = [ev for evs in trace_reduce.device_ops(profile).values() for ev in evs]
    pieces = idle_gaps(ops, window, notes)
    idle: Dict[str, float] = defaultdict(float)
    for name, s, e in pieces:
        idle[name] += (e - s) * 1e-9
    length = (window[1] - window[0]) * 1e-9
    busy = length - sum(idle.values())
    print(f"host_spans: program spans moved {shift * 1e-3:.1f} us; idle "
          f"{length - busy:.6f} s of {length:.6f} s: " + "; ".join(
              f"{k} {v:.6f}" for k, v in sorted(idle.items(), key=lambda kv: -kv[1])),
          file=sys.stderr)
    print("host_spans: longest idle pieces " + "; ".join(
        f"{n} {(e - s) * 1e-9:.6f}" for n, s, e in
        sorted(pieces, key=lambda p: p[1] - p[2])[:10]), file=sys.stderr)
    per_stage = stage_seconds(execs, window)
    print(f"host_spans: device seconds per stage {sum(per_stage.values()):.6f} "
          f"(busy {busy:.6f}): " + "; ".join(
              f"{k} {v:.6f} ({len(execs[k])} runs)" for k, v in per_stage.items()),
          file=sys.stderr)
    print(f"host_spans: longest {longest(doc)}", file=sys.stderr)


if __name__ == "__main__":
    main(*sys.argv[1:3])
