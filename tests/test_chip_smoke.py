"""`chip_smoke.py` refuses to report without a TPU or outside a checkout.

The script is the chip's smoke test; its last line claims a device. These
tests run it where it must fail — JAX held to the CPU, and a directory
holding nothing of the repo but the script — and check it exits non-zero
with no result line on stdout. Nothing here touches an accelerator.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SMOKE = Path(__file__).resolve().parent.parent / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_fails_without_tpu_or_repo(tmp_path, where):
    script = SMOKE
    if where == "alone":
        script = tmp_path / SMOKE.name
        shutil.copy(SMOKE, script)
    res = _run(script, tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    if where == "checkout":
        assert "no TPU" in res.stderr
