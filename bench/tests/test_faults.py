"""A run with the timed path broken underneath reads `correct: false`.

The chip check is skipped (the run is driven on the CPU at a small size);
everything else is a whole run: set-up, window, sample, reference. Faults
a one-chip serving cell can have: an answer left over from an earlier
batch (state not advanced), half of each micro-batch left out, one answer
altered where it is produced. (No exchange between chips exists on one
chip.)"""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

CELLS = [("mobilenet_v2-a1.0-224-w4", "closed64"),
         ("efficientnet_compact-128-w4", "camera-paced")]


def _cell(config, traffic):
    return tiny.cell(config, traffic, **({"rate_per_s": 120} if traffic == "camera-paced" else {}))


def stale_answers(monkeypatch):
    from repro.serve.vision import engine

    orig, last = engine.VisionEngine._record_batch, {}

    def record(self, reqs, y, done):
        prev = last.get("y")
        last["y"] = y
        orig(self, reqs, y if prev is None or prev.shape != y.shape else prev, done)
    monkeypatch.setattr(engine.VisionEngine, "_record_batch", record)


def half_batch_left_out(monkeypatch):
    from repro.serve.vision import engine

    orig = engine.VisionEngine._place

    def place(self, x):
        x = np.array(x)
        x[len(x) - len(x) // 2:] = 0.0
        return orig(self, x)
    monkeypatch.setattr(engine.VisionEngine, "_place", place)


def answer_altered(monkeypatch):
    from repro.serve.vision import stages

    orig = stages.CompiledStage.__call__

    def call(self, x):
        y = orig(self, x)
        if self.spec.dequantizes_output:
            y = y.at[0, 0].add(jnp.float32(self.spec.out_scale))
        return y
    monkeypatch.setattr(stages.CompiledStage, "__call__", call)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_run_is_correct(config, traffic):
    res = tiny.run_cell(_cell(config, traffic))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [stale_answers, half_batch_left_out, answer_altered])
@pytest.mark.parametrize("config,traffic", CELLS)
def test_broken_run_is_not_correct(config, traffic, fault, monkeypatch):
    fault(monkeypatch)
    res = tiny.run_cell(_cell(config, traffic))
    assert not res["correct"]
    assert res["checks"]["mismatched_logits"]["value"] > 0
