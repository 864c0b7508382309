"""Analytic forward+backward operations of one ``alexnet_cifar224`` sample."""

from __future__ import annotations

from benchmark.flops import _count


def products(config):
    widths, inp = config["widths"], config["input"]
    hw = inp["resize_to"] or inp["shape"][0]
    c_in = inp["shape"][2]
    layers = []
    for i, spec in enumerate(widths["conv"]):
        hw = _count.conv_out(hw, spec["kernel"], spec["stride"], spec["pad"])
        layers.append((_count.conv_macs(hw, spec["kernel"], c_in, spec["out"]), i > 0))
        c_in = spec["out"]
        if spec["pool"]:
            hw = _count.conv_out(hw, spec["pool"][0], spec["pool"][1], 0)
    features = widths["avgpool_to"] ** 2 * c_in
    for out in [*widths["classifier"], config["model"]["num_classes"]]:
        layers.append((features * out, True))
        features = out
    return layers


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
