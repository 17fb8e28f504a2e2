"""Stage dispatch (`serve/vision/pipeline.py`): host time spent enqueueing
stage programs, per image dispatched (padding rows included), from the
executor's `dispatch:<cu>` spans and the batch former's `form_batch` spans."""


def read(run):
    dispatch = sum(ev["dur"] for ev in run.spans
                   if ev.get("ph") == "X" and ev["name"].startswith("dispatch:"))
    rows = sum(ev["args"]["bucket"] for ev in run.spans
               if ev.get("ph") == "X" and ev["name"] == "form_batch")
    return dispatch / rows if rows else None
