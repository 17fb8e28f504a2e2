"""A cell on four chips serves over a four-replica 'data' mesh: a whole run
of the tiny MobileNetV2 cell with the closed256 traffic, on four CPU
devices in a process of their own (`mesh_runs.py`)."""
import pytest

import tiny


@pytest.fixture(scope="module")
def sound():
    return tiny.on_cpu_devices("mesh_runs.py", "sound")


def test_four_replica_run_is_correct(sound):
    res = sound["result"]
    assert sound["devices"] == 4 and sound["replicas"] == [4]
    assert res["device"]["count"] == 4
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 256
    assert all(c["value"] == 0 for c in res["checks"].values())


def test_bucket_not_a_multiple_of_chips_is_refused(sound):
    assert sound["refused"] is not None and "multiples" in sound["refused"]
