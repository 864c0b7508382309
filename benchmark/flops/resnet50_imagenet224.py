"""Analytic forward+backward operations of one ``resnet50_imagenet224``
sample (stride on the 3x3 convolution of a downsampling block, as built)."""

from __future__ import annotations

from benchmark.flops import _count


def products(config):
    widths, inp = config["widths"], config["input"]
    hw = inp["resize_to"] or inp["shape"][0]
    stem = widths["stem"]
    hw = _count.conv_out(hw, stem["kernel"], stem["stride"], stem["pad"])
    layers = [(_count.conv_macs(hw, stem["kernel"], inp["shape"][2], stem["out"]), False)]
    hw = _count.conv_out(hw, *stem["pool"])
    c_in, exp = stem["out"], widths["expansion"]
    for stage in widths["stages"]:
        width = stage["width"]
        for block in range(stage["blocks"]):
            stride = stage["stride"] if block == 0 else 1
            out_hw = _count.conv_out(hw, 3, stride, 1)
            layers.append((_count.conv_macs(hw, 1, c_in, width), True))
            layers.append((_count.conv_macs(out_hw, 3, width, width), True))
            layers.append((_count.conv_macs(out_hw, 1, width, width * exp), True))
            if stride != 1 or c_in != width * exp:
                layers.append((_count.conv_macs(out_hw, 1, c_in, width * exp), True))
            hw, c_in = out_hw, width * exp
    layers.append((c_in * config["model"]["num_classes"], True))
    return layers


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
