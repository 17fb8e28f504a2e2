"""MobileNetV2 (Sandler et al. 2018, arXiv:1801.04381, Table 2), as the
DeepDive paper deploys it (arXiv:2007.09490 Sec. 5.1): stem 3x3 conv s2,
17 inverted-residual blocks (1x1 expand, 3x3 depthwise, linear 1x1
project; skip when stride 1 and widths match), 1x1 conv to the last width,
global average pool, dense classifier. First conv at `first_conv_bits`,
the rest at `weight_bits`; ReLU6 everywhere but the projections and the
classifier."""
from __future__ import annotations

from net import CONV, DENSE, DW, NONE, PW, Block, Op, make_divisible


def blocks(cfg):
    a, div = cfg["width_multiplier"], cfg["channel_divisor"]
    wb, ab, fb = cfg["weight_bits"], cfg["activation_bits"], cfg["first_conv_bits"]
    stem = make_divisible(cfg["stem_channels"] * a, div)
    out = [Block("stem", (Op("stem/conv", CONV, cfg["input_channels"], stem,
                             3, 2, "relu6", fb, ab),))]
    cin, idx = stem, 0
    for t, c, n, s in cfg["inverted_residual_settings"]:
        cout = make_divisible(c * a, div)
        for i in range(n):
            stride = s if i == 0 else 1
            name, hidden = f"irb{idx}", cin * t
            ops = []
            if t != 1:
                ops.append(Op(f"{name}/expand", PW, cin, hidden, 1, 1, "relu6", wb, ab))
            ops.append(Op(f"{name}/dw", DW, hidden, hidden, 3, stride, "relu6", wb, ab))
            ops.append(Op(f"{name}/project", PW, hidden, cout, 1, 1, NONE, wb, ab))
            out.append(Block(name, tuple(ops), residual=stride == 1 and cin == cout))
            cin, idx = cout, idx + 1
    last = make_divisible(cfg["last_channels"] * max(1.0, a), div)
    out.append(Block("tail", (Op("tail/pw", PW, cin, last, 1, 1, "relu6", wb, ab),),
                     avgpool=True))
    out.append(Block("classifier", (Op("classifier/fc", DENSE, last,
                                       cfg["num_classes"], 1, 1, NONE, wb, ab),)))
    return out


def program_netspec(cfg):
    from repro.configs import mobilenet_v2

    return mobilenet_v2.get_config(
        alpha=cfg["width_multiplier"], input_hw=cfg["input_hw"],
        bits=cfg["weight_bits"], first_conv_bits=cfg["first_conv_bits"],
        num_classes=cfg["num_classes"], round_nearest=cfg["channel_divisor"])
