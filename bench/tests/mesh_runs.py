"""Whole runs of the tiny MobileNetV2 cell over a four-device 'data' mesh,
for the tests that need four devices (`tiny.on_cpu_devices` starts it with
`XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu`):

    python bench/tests/mesh_runs.py sound   # a sound run, and a refused bucket
    python bench/tests/mesh_runs.py faults  # one run per fault of test_faults.py

The last line of standard output is one JSON object."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

import tiny  # noqa: E402

CHIPS = 4


def closed256(**traffic):
    # a drain of 256 frames is 8 micro-batches of 32, 8 rows on each device
    return tiny.cell("mobilenet_v2-a1.0-224-w4", "closed256", chips=CHIPS, **traffic)


def sound() -> dict:
    from repro.serve.vision import VisionEngine

    engines, init = [], VisionEngine.__init__

    def keep(self, *a, **kw):
        init(self, *a, **kw)
        engines.append(self)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(VisionEngine, "__init__", keep)
        res = tiny.run_cell(closed256(), seconds=0.5)
    try:
        tiny.run_cell(closed256(buckets=[30]), seconds=0.5)
        refused = None
    except SystemExit as e:
        refused = str(e)
    return {"result": res, "replicas": [e.replicas for e in engines],
            "devices": len(jax.devices()), "refused": refused}


def faults() -> dict:
    import test_faults

    out = {}
    for fault in test_faults.FOUR_CHIP_FAULTS:
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            out[fault.__name__] = tiny.run_cell(closed256(), seconds=0.5)
    return out


if __name__ == "__main__":
    print(json.dumps({"sound": sound, "faults": faults}[sys.argv[1]]()))
