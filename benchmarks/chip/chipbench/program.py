"""The system under test: the program's training step for one cell.

This is the only module of the benchmark that imports the program.  The
step is what ``chip_smoke.py`` trains: ``jax.value_and_grad`` of
``CNNModel.loss`` under the config's sparsity policy, then plain SGD, on
one chip.
"""
from __future__ import annotations

from typing import Callable

import jax


def sgd(params, grads, lr: float):
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def build_step(config: dict) -> Callable:
    """``step(params, images, labels) -> (new_params, loss)``, jitted."""
    from repro.core import policy as pol
    from repro.models.cnn import build_cnn

    model = build_cnn(config["net"], image_size=config["image_size"],
                      width=config["width"],
                      num_classes=config["num_classes"])
    policy = getattr(pol, config["policy"]).with_(
        kernel_impl=config["kernel_impl"])
    lr = config["lr"]

    def step(params, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, images, labels, policy))(params)
        return sgd(params, grads, lr), loss
    return jax.jit(step)
