"""Cut a small trace fixture out of a profiler trace that `bench/run.py
--trace 1 --keep-trace DIR` recorded on the chip:

    python bench/tests/make_trace_fixture.py DIR OUT.pbtxt [drains]

It keeps the first `drains` (default 2) drains of the profiled slice: the
benchmark's host annotations (`DIR/notes.json`, on the trace's clock), every
device op event that overlaps them, and a `bench.window` annotation around
them, as an XSpace text proto that `jax.profiler.ProfileData.from_text_proto`
reads back (host annotations on the host plane's `python` line)."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402


def main(src: str, out: str, drains: int = 2) -> None:
    profile = trace_reduce.load(str(sorted(Path(src).rglob("*.xplane.pb"))[-1]))
    with open(Path(src) / "notes.json") as f:
        kept = json.load(f)
    lo_w, hi_w = kept["window"]
    inside = sorted(tuple(a) for a in kept["notes"] if lo_w <= a[1] and a[2] <= hi_w)
    ends = [e for n, s, e in sorted(inside, key=lambda a: a[1]) if n == "bench.drain"]
    lo = min(s for _, s, _ in inside)
    hi = ends[drains - 1]
    host = [("bench.window", lo, hi)] + [a for a in inside if a[2] <= hi]
    planes = {"/host:CPU": {"python": host}}
    for plane, evs in trace_reduce.device_ops(profile).items():
        planes[plane] = {trace_reduce.OPS_LINE: [e for e in evs if e[2] > lo and e[1] < hi]}
    text = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        text.append(f"planes {{\n  id: {pid}\n  name: {json.dumps(plane)}")
        names = {}
        for lid, (line, evs) in enumerate(lines.items(), 1):
            text.append(f"  lines {{\n    id: {lid}\n    name: {json.dumps(line)}\n"
                        f"    timestamp_ns: 0")
            for name, s, e in evs:
                mid = names.setdefault(name, len(names) + 1)
                text.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{round((s - lo) * 1000)} duration_ps: {round((e - s) * 1000)} }}")
            text.append("  }")
        for name, mid in names.items():
            text.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                        f"name: {json.dumps(name)} }} }}")
        text.append("}")
    Path(out).write_text("\n".join(text) + "\n")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:]))
