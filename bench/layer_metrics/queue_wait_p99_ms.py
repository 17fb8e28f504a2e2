"""Front end (`serve/vision/engine.py`): 99th percentile of the time each
request waited from its scheduled arrival until a micro-batch took it,
from the engine's `queue_wait` spans (one begin/end pair per request)."""
from stats import percentile


def read(run):
    begin, waits = {}, []
    for ev in run.spans:
        if ev.get("name") != "queue_wait":
            continue
        key = (ev["cat"], ev["id"])
        if ev["ph"] == "b":
            begin[key] = ev["ts"]
        elif ev["ph"] == "e" and key in begin:
            waits.append(ev["ts"] - begin.pop(key))
    return percentile(waits, 0.99) * 1e-3 if waits else None
