"""Compact EfficientNet as the DeepDive paper deploys it (arXiv:2007.09490
Sec. 5.2), compound-scaled from EfficientNet-B0's stages (Tan & Le 2019,
arXiv:1905.11946, Table 1): stem 3x3 conv s2, MBConv blocks (1x1 expand,
KxK depthwise, squeeze-excitation on the depthwise output, linear 1x1
project; skip when stride 1 and widths match), 1x1 conv to the head width,
global average pool, dense classifier. The paper's SE uses ReLU6 in the
squeeze and a hard sigmoid (ReLU6(x + 3) / 6) as the gate."""
from __future__ import annotations

import math

from net import CONV, DENSE, DW, HSIGMOID, NONE, PW, SE, Block, Op, make_divisible


def blocks(cfg):
    w, d, div = cfg["width"], cfg["depth"], cfg["channel_divisor"]
    wb, ab, fb = cfg["weight_bits"], cfg["activation_bits"], cfg["first_conv_bits"]
    stem = make_divisible(cfg["stem_channels"] * w, div)
    out = [Block("stem", (Op("stem/conv", CONV, cfg["input_channels"], stem,
                             3, 2, "relu6", fb, ab),))]
    cin, idx = stem, 0
    for t, c, n, s, k in cfg["stage_settings"]:
        cout = make_divisible(c * w, div)
        for i in range(int(math.ceil(n * d))):
            stride = s if i == 0 else 1
            name, hidden = f"mb{idx}", cin * t
            ops = []
            if t != 1:
                ops.append(Op(f"{name}/expand", PW, cin, hidden, 1, 1, "relu6", wb, ab))
            ops.append(Op(f"{name}/dw", DW, hidden, hidden, k, stride, "relu6", wb, ab))
            ops.append(Op(f"{name}/project", PW, hidden, cout, 1, 1, NONE, wb, ab))
            red = max(1, int(cin * cfg["se_ratio"]))
            se = SE(Op(f"{name}/se/pw_sq", PW, hidden, red, 1, 1, "relu6", wb, wb),
                    Op(f"{name}/se/pw_ex", PW, red, hidden, 1, 1, HSIGMOID, wb, wb),
                    f"{name}/dw")
            out.append(Block(name, tuple(ops), residual=stride == 1 and cin == cout,
                             se=se))
            cin, idx = cout, idx + 1
    head = make_divisible(cfg["head_channels"] * w, div)
    out.append(Block("tail", (Op("tail/pw", PW, cin, head, 1, 1, "relu6", wb, ab),),
                     avgpool=True))
    out.append(Block("classifier", (Op("classifier/fc", DENSE, head,
                                       cfg["num_classes"], 1, 1, NONE, wb, ab),)))
    return out


def program_netspec(cfg):
    from repro.configs import efficientnet_compact

    return efficientnet_compact.get_config(
        input_hw=cfg["input_hw"], bits=cfg["weight_bits"],
        num_classes=cfg["num_classes"])
