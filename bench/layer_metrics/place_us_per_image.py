"""Front end (`serve/vision/engine.py`): host time spent placing each
micro-batch on the device (`_place`: the float32 images' layout and
upload), per image dispatched (padding rows included), from the engine's
`place` spans. A trace without one `place` span per `form_batch` span
reads nothing."""


def read(run):
    spans = [ev for ev in run.spans if ev.get("ph") == "X"]
    place = [ev for ev in spans if ev["name"] == "place"]
    batches = sum(1 for ev in spans if ev["name"] == "form_batch")
    if not place or len(place) != batches:
        return None
    return sum(ev["dur"] for ev in place) / sum(ev["args"]["rows"] for ev in place)
