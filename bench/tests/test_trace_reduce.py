"""Trace reduction on a recorded trace and on hand-made intervals."""
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as T

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "effn_trace.pbtxt"


@pytest.fixture(scope="module")
def profile():
    import jax

    return jax.profiler.ProfileData.from_text_proto(FIXTURE.read_text())


def test_union_and_gaps_by_hand():
    merged = T.union([(5, 6), (0, 2), (1, 3), (9, 12)], 0, 10)
    assert merged == [(0, 3), (5, 6), (9, 10)]
    assert T.gaps(merged, 0, 10) == [(3, 5), (6, 9)]
    assert T.gaps([], 0, 10) == [(0, 10)]


def test_idle_share_is_one_minus_union_over_window():
    # two overlapping ops count once; the window clips the last one
    ops = [("%a.1 = f32[] fusion()", 0.0, 4.0), ("%b = f32[] fusion()", 2.0, 6.0),
           ("%fused_irb_q.7 = s32[8] custom-call()", 8.0, 20.0)]
    merged = T.union([(s, e) for _, s, e in ops], 0.0, 10.0)
    busy = sum(e - s for s, e in merged)
    assert busy == 8.0  # [0, 6] and [8, 10]
    assert 1 - busy / 10.0 == pytest.approx(0.2)
    assert T.short_name(ops[2][0]) == "fused_irb_q"
    assert T.short_name("%pad.341.clone = s32[256] pad()") == "pad"


def test_recorded_trace(profile):
    red = T.reduce(profile)
    assert red is not None and red.device_count == 1
    notes = T.annotations(profile)
    (lo, hi), = [(s, e) for n, s, e in notes if n == T.WINDOW]
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # busy time, recomputed on a 1-ns grid from the raw events
    ops = T.device_ops(profile)["/device:TPU:0"]
    grid = np.zeros(int(hi - lo) + 2, bool)
    for _, s, e in ops:
        grid[max(0, int(round(s - lo))):max(0, int(round(min(e, hi) - lo)))] = True
    assert red.busy_s == pytest.approx(grid.sum() * 1e-9, rel=1e-3)
    assert 0 < red.busy_s < red.window_s
    # EfficientNet-compact: 10 depthwise kernel calls per micro-batch of 8,
    # two drains of 64 frames in the fixture
    drains = [a for a in notes if a[0] == "bench.drain"]
    assert len(drains) == 2
    assert len(red.kernel("depthwise_conv_q")) == 10 * 2 * 8
    assert red.kernel("fused_irb_q") == []
    names = [n for n, _ in red.top_ops()]
    assert "depthwise_conv_q" in names and "pointwise_conv_q" in names
    assert all(label.startswith("bench.") or label == "outside bench annotations"
               for label, _ in red.idle_gaps)
    assert [g for _, g in red.idle_gaps] == sorted((g for _, g in red.idle_gaps),
                                                   reverse=True)


def test_reduce_with_given_window_and_notes(profile):
    notes = T.annotations(profile)
    (lo, hi), = [(s, e) for n, s, e in notes if n == T.WINDOW]
    half = (lo, (lo + hi) / 2)
    red = T.reduce(profile, window=half, notes=[])
    assert red.window_s == pytest.approx((half[1] - half[0]) * 1e-9)
    assert all(label == "outside bench annotations" for label, _ in red.idle_gaps)
    assert T.reduce(profile, notes=[]) is None  # no window to reduce over


@pytest.mark.parametrize("chips", [1, 4])
def test_mfu_divides_by_the_cells_chips(profile, chips):
    import counts
    import net
    import run

    red = T.reduce(profile)
    cfg = net.load_config(net.BENCH / "configs" / "efficientnet_compact-128-w4.json")
    peak = counts.load_peak("TPU v5 lite")
    images = 2 * 64  # the fixture's two drains
    inputs = run.LayerInputs(
        blocks=[], input_hw=cfg["input_hw"], peak=peak,
        macs_per_image=cfg["macs_per_image"], trace=red, traced_batches=[8] * 16,
        traced_images=images, spans=[], chips=chips)
    one_chip = 100 * 2 * cfg["macs_per_image"] * images / (
        red.window_s * peak["int8_ops_per_s"])  # the one-chip formula
    assert run.reader("mfu")(inputs) == one_chip / chips
