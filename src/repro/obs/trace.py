"""Span-based request-lifecycle tracer, exported as Chrome trace-event JSON.

The paper measures *where* time goes on real hardware (per-CU invocation
latency over AXI, DDR stalls); the serving analogue is a trace of the
request lifecycle through the pipelined executor: submit -> queue wait ->
batch formation -> per-stage CU dispatch -> harvest -> complete. This
module records those spans in the Chrome trace-event format ("Trace Event
Format", the `traceEvents` JSON array), which Perfetto / chrome://tracing
load directly — drop the file into https://ui.perfetto.dev and every
track/span below renders on a timeline.

Design constraints, in order:

  * **Injectable clock.** Every timestamp comes either from an explicit
    caller-supplied time (the engine records spans with ITS clock, so one
    time source rules engine stats, deadlines, and trace alike) or from the
    tracer's own clock, which tests replace with a fake — the exported
    trace of a fake-clock run is byte-deterministic.
  * **Cheap when off.** `NULL` is a no-op tracer that is falsy; hot-path
    call sites guard their extra clock reads with `if tracer:` so a
    tracing-disabled engine performs exactly the clock reads it always did.
  * **Cheap when on.** Events are flat tuples until export (see `Tracer`);
    the collector stops walking them after one collection.
  * **Zero dependencies.** Export is plain dicts through `json.dump`.

Event vocabulary (all standard trace-event phases):

  * `complete(name, t0, t1)`    -> "X" duration span on a named track
  * `instant(name, t)`          -> "i" instant marker
  * `counter(name, {k: v}, t)`  -> "C" counter track (e.g. queue depth)
  * `async_begin/async_end`     -> "b"/"e" async spans keyed by id: one
                                   per-request lifecycle span that overlaps
                                   freely with other requests
  * `name_track(tid, name)`     -> "M" metadata naming a track
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

# Well-known track ids for the serving pipeline (metadata-named on first
# use; stage executors get TID_STAGE0 + stage index).
TID_ENGINE = 0
TID_REQUESTS = 1
TID_SCHED = 2
TID_TUNE = 3
TID_TRAIN = 4
TID_STAGE0 = 10


class NullTracer:
    """No-op tracer: every record method does nothing, truthiness is False
    so call sites can skip the extra clock reads tracing needs."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def now(self) -> float:
        return 0.0

    def complete(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def counter(self, *a, **k) -> None:
        pass

    def async_begin(self, *a, **k) -> None:
        pass

    def async_end(self, *a, **k) -> None:
        pass

    def name_track(self, *a, **k) -> None:
        pass

    @contextmanager
    def span(self, *a, **k):
        yield

    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": []}

    def save(self, path: str) -> None:
        raise ValueError("cannot save the null tracer (tracing is off)")


NULL = NullTracer()

# (phase, name, cat, tid, t0_s, t1_s, async id, key1, value1, key2, ...)
_Event = Tuple[Any, ...]


def _flat(args: Optional[Dict[str, Any]]) -> Tuple[Any, ...]:
    """Args as alternating keys and values, to append to the event tuple:
    a collection untracks a tuple only once nothing in it is tracked, one
    level per pass, so the event stays one level deep."""
    return sum(args.items(), ()) if args else ()


class Tracer:
    """Collects trace events; `to_chrome()`/`save()` export Perfetto JSON.

    `clock` returns seconds (perf_counter-like). Timestamps passed to the
    record methods are in the SAME time base as `clock`; the tracer
    subtracts its construction-time origin and scales to microseconds (the
    trace-event unit). `pid` tags every event (one tracer per process is
    the normal shape; a shared tracer across engines puts them on one
    timeline, which is exactly what the multi-model router wants).

    Each event is stored as one flat tuple of atoms (`_Event`: phase, name,
    category, track, raw times, async id, then arg keys and values in
    turn); Chrome dicts are built only on export. A tuple that holds no
    container the collector tracks is untracked at its first garbage
    collection, so a long traced run leaves the collector almost nothing
    to walk (call sites keep arg values to numbers, strings and, once per
    micro-batch, a tuple of request ids, untracked one pass later).

    With the default clock the tracer also notes its origin on the wall
    clock (`origin_unix_ns`, exported as `otherData.origin_unix_ns`):
    `origin_unix_ns + ts * 1e3` puts a span on the `time.time_ns` clock a
    profiler session is stamped with. An injected clock has no wall-clock
    meaning, so fake-clock exports carry no such field."""

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 *, process_name: str = "repro-serve", pid: int = 0,
                 origin_s: Optional[float] = None):
        self._clock = time.perf_counter if clock is None else clock
        self._origin = self._clock() if origin_s is None else origin_s
        self.origin_unix_ns: Optional[int] = None
        if clock is None:
            wall = time.time_ns()
            self.origin_unix_ns = wall - round(
                (time.perf_counter() - self._origin) * 1e9)
        self.pid = pid
        self.events: List[_Event] = []
        self._tracks: Dict[int, str] = {}
        self._meta: List[Dict[str, Any]] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        }]

    def __bool__(self) -> bool:
        return True

    def __len__(self) -> int:
        return len(self.events)

    def now(self) -> float:
        return self._clock()

    # -- record methods ----------------------------------------------------

    def name_track(self, tid: int, name: str) -> None:
        if self._tracks.get(tid) == name:
            return
        self._tracks[tid] = name
        self._meta.append({
            "ph": "M", "name": "thread_name", "pid": self.pid, "tid": tid,
            "args": {"name": name},
        })

    def complete(self, name: str, start_s: float, end_s: float, *,
                 cat: str = "", tid: int = TID_ENGINE,
                 args: Optional[Dict[str, Any]] = None) -> None:
        """One finished span with explicit start/end times ("X" event)."""
        self.events.append(("X", name, cat, tid, start_s, end_s, None,
                            *_flat(args)))

    def instant(self, name: str, t_s: Optional[float] = None, *,
                cat: str = "", tid: int = TID_ENGINE,
                args: Optional[Dict[str, Any]] = None) -> None:
        t = self._clock() if t_s is None else t_s
        self.events.append(("i", name, cat, tid, t, None, None, *_flat(args)))

    def counter(self, name: str, values: Dict[str, float],
                t_s: Optional[float] = None, *, tid: int = TID_ENGINE) -> None:
        t = self._clock() if t_s is None else t_s
        self.events.append(("C", name, "", tid, t, None, None,
                            *_flat(values)))

    def async_begin(self, name: str, span_id: int,
                    t_s: Optional[float] = None, *, cat: str = "request",
                    args: Optional[Dict[str, Any]] = None) -> None:
        """Open an async span (nestable "b"); pairs with `async_end` by
        (cat, id) — the per-request lifecycle span, one id per rid."""
        t = self._clock() if t_s is None else t_s
        self.events.append(("b", name, cat, TID_REQUESTS, t, None, span_id,
                            *_flat(args)))

    def async_end(self, name: str, span_id: int,
                  t_s: Optional[float] = None, *, cat: str = "request",
                  args: Optional[Dict[str, Any]] = None) -> None:
        t = self._clock() if t_s is None else t_s
        self.events.append(("e", name, cat, TID_REQUESTS, t, None, span_id,
                            *_flat(args)))

    @contextmanager
    def span(self, name: str, *, cat: str = "", tid: int = TID_ENGINE,
             args: Optional[Dict[str, Any]] = None):
        """Context-managed span timed on the tracer's own clock (for call
        sites without their own time source, e.g. the tuner / trainer)."""
        t0 = self._clock()
        try:
            yield
        finally:
            self.complete(name, t0, self._clock(), cat=cat, tid=tid,
                          args=args)

    # -- export ------------------------------------------------------------

    def _chrome(self, event: _Event) -> Dict[str, Any]:
        ph, name, cat, tid, t0, t1, span_id = event[:7]
        ts = (t0 - self._origin) * 1e6
        if ph == "X":
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "cat": cat, "pid": self.pid,
                "tid": tid, "ts": ts, "dur": max(0.0, (t1 - t0) * 1e6)}
        elif ph == "i":
            ev = {"ph": ph, "name": name, "cat": cat, "pid": self.pid,
                  "tid": tid, "ts": ts, "s": "t"}
        elif ph == "C":
            return {"ph": ph, "name": name, "pid": self.pid, "tid": tid,
                    "ts": ts, "args": dict(zip(event[7::2], event[8::2]))}
        else:  # "b" / "e"
            ev = {"ph": ph, "name": name, "cat": cat, "id": span_id,
                  "pid": self.pid, "tid": tid, "ts": ts}
        if len(event) > 7:
            ev["args"] = dict(zip(event[7::2], event[8::2]))
        return ev

    def to_chrome(self) -> Dict[str, Any]:
        """The Perfetto-loadable document: metadata first (track names),
        then events in record order (the format does not require sorting)."""
        doc: Dict[str, Any] = {
            "traceEvents": self._meta + [self._chrome(e) for e in self.events],
            "displayTimeUnit": "ms",
        }
        if self.origin_unix_ns is not None:
            doc["otherData"] = {"origin_unix_ns": self.origin_unix_ns}
        return doc

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, allow_nan=False)
        return path


def validate_chrome_trace(doc: Any) -> List[str]:
    """Schema check over an exported trace document; returns the list of
    violations (empty == loadable). This is what the CI bench-smoke job and
    `python -m repro.obs validate` run against the artifact it uploads."""
    errors: List[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document is not an object with a 'traceEvents' array"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' is not an array"]
    open_async: Dict[tuple, int] = {}
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "i", "C", "b", "e", "M"):
            errors.append(f"{where}: unknown phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"{where}: missing name")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                errors.append(f"{where}: missing integer {key!r}")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            errors.append(f"{where}: missing numeric ts")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: X event needs dur >= 0")
        if ph == "C" and not isinstance(ev.get("args"), dict):
            errors.append(f"{where}: C event needs an args value dict")
        if ph in ("b", "e"):
            if "id" not in ev or not ev.get("cat"):
                errors.append(f"{where}: async event needs id and cat")
            else:
                key = (ev["cat"], ev["id"], ev["name"])
                if ph == "b":
                    open_async[key] = open_async.get(key, 0) + 1
                else:
                    n = open_async.get(key, 0)
                    if n <= 0:
                        errors.append(f"{where}: async end without begin "
                                      f"for {key}")
                    else:
                        open_async[key] = n - 1
    for key, n in sorted(open_async.items()):
        if n > 0:
            errors.append(f"async span {key} opened {n} time(s) without end")
    return errors


__all__ = [
    "NULL",
    "NullTracer",
    "TID_ENGINE",
    "TID_REQUESTS",
    "TID_SCHED",
    "TID_STAGE0",
    "TID_TRAIN",
    "TID_TUNE",
    "Tracer",
    "validate_chrome_trace",
]
