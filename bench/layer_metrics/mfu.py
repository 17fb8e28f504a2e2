"""Model step (`serve/vision/stages.py`): the whole served step's share of
the int8 peak of the cell's chips, 2 x MACs per image x images answered in
the profiled slice, over the slice's length x chips x one chip's peak.
Bounds every kernel roofline."""


def read(run):
    if run.trace is None or not run.traced_images:
        return None
    ops = 2 * run.macs_per_image * run.traced_images
    return 100 * ops / (run.trace.window_s * run.peak["int8_ops_per_s"] * run.chips)
