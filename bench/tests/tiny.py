"""Cells at a size a CPU test run holds: the real configuration and traffic
files with the widths, resolution and classes cut down."""
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


def cell(config_name, traffic_name, **traffic):
    t = trafficlib.load(traffic_name)
    t.update(traffic)
    e2e = [{"name": "setup_s", "unit": "s"}, {"name": "images_per_s", "unit": "images/s"}]
    return run.Cell(f"tiny.{traffic_name}", 1, config_name, config(config_name), t, e2e, [])


def run_cell(c, seed=20260101, seconds=1.0, trace=False):
    return run.run_cell(c, seed, seconds, trace, jax.devices()[:1], run.T_START)
