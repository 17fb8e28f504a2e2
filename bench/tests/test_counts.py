"""Analytic work counts and the peaks table, checked on hand-worked ops."""
import json

import pytest

import counts
import net
from net import Op

V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_depthwise_op_by_hand():
    # 5x5 stride-2 depthwise, 32x32x240 -> 16x16x240, a micro-batch of 8
    op = Op("b/dw", "dw", 240, 240, 5, 2)
    ops, nbytes = counts.op_work(op, 32, 16, rows=8)
    assert ops == 2 * 8 * (16 * 16 * 25 * 240) == 24_576_000
    # activations in + out (1 byte each), 25 x 240 weights, 12 B per channel
    assert nbytes == 8 * (32 * 32 * 240 + 16 * 16 * 240) + 25 * 240 + 12 * 240
    assert nbytes == 2_466_480
    # memory bound on a v5e: 2,466,480 B / 819 GB/s
    assert counts.least_seconds(ops, nbytes, V5E) == pytest.approx(3.0115751e-6)


def test_fused_irb_block_by_hand():
    # MobileNetV2 irb2: 56x56x24 -> expand 144 -> 3x3 dw -> project 24
    block = [(Op("irb2/expand", "pw", 24, 144), 56, 56),
             (Op("irb2/dw", "dw", 144, 144, 3, 1), 56, 56),
             (Op("irb2/project", "pw", 144, 24, act="none"), 56, 56)]
    ops, nbytes = counts.fused_block_work(block, rows=8)
    macs = 3136 * 24 * 144 + 3136 * 9 * 144 + 3136 * 144 * 24
    assert macs == 25_740_288
    assert ops == 2 * 8 * macs
    # only the block's input and output move; weights and constants once
    assert nbytes == 8 * (3136 * 24 + 3136 * 24) + (3456 + 1296 + 3456) + 12 * 312
    assert nbytes == 1_216_176
    assert counts.least_seconds(ops, nbytes, V5E) == pytest.approx(1.4849524e-6)


@pytest.mark.parametrize("name", ["mobilenet_v2-a1.0-224-w4",
                                  "efficientnet_compact-128-w4"])
def test_config_macs_match_the_file_and_the_program(name):
    cfg = net.load_config(net.BENCH / "configs" / f"{name}.json")
    fam = net.family(cfg)
    blocks = fam.blocks(cfg)
    program = fam.program_netspec(cfg)
    net.check_same(blocks, program)
    macs = counts.macs_per_image(blocks, cfg["input_hw"])
    assert macs == cfg["macs_per_image"] == program.count_macs()


def test_fusable_blocks_per_config():
    def fused(name):
        cfg = net.load_config(net.BENCH / "configs" / f"{name}.json")
        return sum(counts.fusable(b) for b in net.family(cfg).blocks(cfg))

    assert fused("mobilenet_v2-a1.0-224-w4") == 16  # irb1..irb16
    assert fused("efficientnet_compact-128-w4") == 0  # every block has SE


def test_peaks_table_and_unknown_device():
    peak = counts.load_peak("TPU v5 lite")
    assert peak["int8_ops_per_s"] == 393e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peak["source"]
    with pytest.raises(SystemExit):
        counts.load_peak("TPU v9 imaginary")
    with open(net.BENCH / "peaks.json") as f:
        assert all("source" in v for v in json.load(f).values())
