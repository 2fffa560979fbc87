"""Dense model FLOPs of one training step, counted from a config's table.

A conv of k x k x c -> m at an output of u x u pixels does u*u*k*k*c*m
multiply-adds per image in each of its three GEMMs: forward, input
gradient (dX) and weight gradient (dW).  The first conv's input is the
image, whose gradient nobody needs, so it has no dX.  The head adds
f x classes in each of its three.  A multiply-add is two FLOPs.  Pooling,
BatchNorm, ReLU and the loss are left out: they do no matrix work.  This
counts the work the dense model requires, not what the sparse kernels
skip, so it is the same for every implementation.
"""
from __future__ import annotations

from chipbench.layers import conv_out_hw, final_channels, iter_convs


def conv_macs(node: dict) -> int:
    u = conv_out_hw(node)
    return u * u * node["kernel"] ** 2 * node["in_ch"] * node["out_ch"]


def train_flops_per_image(config: dict) -> int:
    total = 0
    for i, node in enumerate(iter_convs(config["layers"])):
        total += 2 * conv_macs(node) * (2 if i == 0 else 3)
    total += 2 * final_channels(config["layers"]) * config["num_classes"] * 3
    return total
