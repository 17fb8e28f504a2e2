"""A run with the timed path broken underneath reads `correct: false`.

The chip check is skipped (the run is driven on the CPU at a small size);
everything else is a whole run: set-up, window, sample, reference. Faults
a one-chip serving cell can have: an answer left over from an earlier
batch (state not advanced), half of each micro-batch left out, one answer
altered where it is produced. A cell on a four-chip 'data' mesh can have
each of those, and one more: the exchange between chips left out, so that
one replica's answers go back to another replica's requests. Those runs see
four CPU devices, in a process of their own (`mesh_runs.py`)."""
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


def replica_answers_swapped(monkeypatch):
    from repro.serve.vision import stages

    orig = stages.CompiledStage.__call__

    def call(self, x):
        y = orig(self, x)
        if self.spec.dequantizes_output and self.mesh is not None:
            # each replica's rows land on the next replica's requests
            y = jnp.roll(y, len(y) // self.mesh.shape["data"], axis=0)
        return y
    monkeypatch.setattr(stages.CompiledStage, "__call__", call)


ONE_CHIP_FAULTS = [stale_answers, half_batch_left_out, answer_altered]
FOUR_CHIP_FAULTS = ONE_CHIP_FAULTS + [replica_answers_swapped]


@pytest.fixture(scope="module")
def four_chip_runs():
    return tiny.on_cpu_devices("mesh_runs.py", "faults")


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_run_is_correct(config, traffic):
    res = tiny.run_cell(_cell(config, traffic))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
@pytest.mark.parametrize("config,traffic", CELLS)
def test_broken_run_is_not_correct(config, traffic, fault, monkeypatch):
    fault(monkeypatch)
    res = tiny.run_cell(_cell(config, traffic))
    assert not res["correct"]
    assert res["checks"]["mismatched_logits"]["value"] > 0


@pytest.mark.parametrize("fault", [f.__name__ for f in FOUR_CHIP_FAULTS])
def test_broken_four_chip_run_is_not_correct(fault, four_chip_runs):
    res = four_chip_runs[fault]
    assert res["device"]["count"] == 4
    assert not res["correct"]
    assert res["checks"]["mismatched_logits"]["value"] > 0
