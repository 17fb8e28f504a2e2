"""Reduce a JAX profiler trace (`jax.profiler.ProfileData`) to metrics.

Device planes (`/device:TPU:<n>`) carry one event per XLA operation on the
line `XLA Ops`, named by its HLO instruction text
(`%fused_irb_q.3 = s32[...] custom-call(...)`); host annotations
(`jax.profiler.TraceAnnotation`) sit on a host plane's `python` line. Event
times are nanoseconds after the session's wall-clock start
(`profile_start_time` on the `Task Environment` plane).

- busy: the union of a device's op intervals inside the profiled window
  (given, or the host annotation `bench.window`);
- idle gaps: the holes in that union, each named by the innermost
  `bench.*` annotation that covers its midpoint (given, or read from the
  trace's host plane);
- per-kernel sums: the op events of one HLO instruction name, over the
  whole trace (the harness starts and stops the profiler between drains,
  so every device op in it belongs to a profiled drain).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
ANNOTATION_PREFIX = "bench."
_INSTR = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.clone)? = ")
_RESULT = re.compile(r"^%\S+ = (\(?[a-z0-9]+\[[\d,]*\])")

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)


def short_name(event_name: str) -> str:
    """HLO instruction name without its numeric suffix:
    '%fused_irb_q.3 = s32[...] ...' -> 'fused_irb_q'."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def _events(line) -> List[Event]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def device_ops(profile) -> Dict[str, List[Event]]:
    """Device plane name -> its XLA op events."""
    out = {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name] = _events(line)
    return out


def profile_start_ns(profile) -> Optional[int]:
    """Wall-clock ns (time.time_ns) at which the profiling session began."""
    for plane in profile.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                return int(value)
    return None


def annotations(profile) -> List[Event]:
    """Every host event whose name starts with `bench.`."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [e for e in _events(line)
                        if e[0].startswith(ANNOTATION_PREFIX)]
    return out


def union(intervals: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(merged: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, notes: List[Event]) -> str:
    """The innermost annotation covering time t."""
    cover = [(e - s, n) for n, s, e in notes if s <= t <= e]
    return min(cover)[1] if cover else "outside bench annotations"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the device planes
    ops: List[Event]  # every device op event of the trace
    idle_gaps: List[Tuple[str, float]]  # longest first
    device_count: int

    def kernel(self, name: str) -> List[Event]:
        """Op events of one HLO instruction name (`short_name`)."""
        return [e for e in self.ops if short_name(e[0]) == name]

    def top_ops(self, n: int = 10, shapes: bool = False) -> List[Tuple[str, float]]:
        """Device seconds per op name (with `shapes`, per name and result
        type), largest first."""
        total: Dict[str, float] = defaultdict(float)
        for name, s, e in self.ops:
            key = short_name(name)
            if shapes:
                m = _RESULT.match(name)
                key += " " + m.group(1) if m else ""
            total[key] += (e - s) * 1e-9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def reduce(profile, window: Optional[Tuple[float, float]] = None,
           notes: Optional[List[Event]] = None, n_gaps: int = 10) -> Optional[Reduced]:
    """Reduce the device ops inside `window` (trace ns), naming idle gaps by
    `notes` (name, start, end in trace ns). Without them both come from the
    trace's own host annotations (`bench.window` and the other `bench.*`).
    None when there is no window or no device op in it."""
    if notes is None:
        notes = annotations(profile)
    if window is None:
        win = [(s, e) for n, s, e in notes if n == WINDOW]
        if len(win) != 1:
            return None
        window = win[0]
    devices = device_ops(profile)
    if not devices:
        return None
    lo, hi = window
    inner = [a for a in notes if a[0] != WINDOW]
    busy, ops, holes = 0.0, [], []
    for evs in devices.values():
        merged = union([(s, e) for _, s, e in evs], lo, hi)
        busy += sum(e - s for s, e in merged)
        ops += evs
        holes += [(label((s + e) / 2, inner), (e - s) * 1e-9)
                  for s, e in gaps(merged, lo, hi)]
    if not ops:
        return None
    holes.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(devices),
                   ops=ops, idle_gaps=holes[:n_gaps], device_count=len(devices))


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)
