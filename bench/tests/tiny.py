"""Cells at a size a CPU test run holds: the real configuration and traffic
files with the widths, resolution and classes cut down."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

import counts
import net
import run
import traffic as trafficlib

SHRINK = {"mobilenet_v2-a1.0-224-w4": {"width_multiplier": 0.35, "input_hw": 32,
                                       "num_classes": 10},
          "efficientnet_compact-128-w4": {"input_hw": 32, "num_classes": 10}}


def config(name):
    cfg = net.load_config(net.BENCH / "configs" / f"{name}.json")
    cfg.update(SHRINK[name])
    cfg["macs_per_image"] = counts.macs_per_image(net.family(cfg).blocks(cfg),
                                                  cfg["input_hw"])
    return cfg


def cell(config_name, traffic_name, chips=1, **traffic):
    t = trafficlib.load(traffic_name)
    t.update(traffic)
    e2e = [{"name": "setup_s", "unit": "s"}, {"name": "images_per_s", "unit": "images/s"}]
    return run.Cell(f"tiny.{traffic_name}", chips, config_name, config(config_name), t,
                    e2e, [])


def run_cell(c, seed=20260101, seconds=1.0, trace=False):
    return run.run_cell(c, seed, seconds, trace, jax.devices()[:c.chips], run.T_START)


def on_cpu_devices(script: str, *args: str, devices: int = 4, timeout: int = 900):
    """Run `bench/tests/<script>` in a fresh process that sees `devices` CPU
    devices (the count is fixed before JAX starts, so not in this process);
    the JSON object on its last line of standard output."""
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, str(here / script), *args], cwd=here,
                         capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
