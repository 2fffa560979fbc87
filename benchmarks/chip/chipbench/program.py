"""The system under test: the program's training step for one cell.

This is the only module of the benchmark that imports the program.  The
step is what ``chip_smoke.py`` trains: ``jax.value_and_grad`` of
``CNNModel.loss`` under the config's sparsity policy, then plain SGD, on
one chip; on several, the gradient of the program's own data-parallel step
(``repro.sharding.spmd_step.make_spmd_grad_fn`` at its defaults: the batch
split over the mesh, the gradient all-reduced by
``collectives.psum_grads``), then the same SGD in the same ``jax.jit``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax


def sgd(params, grads, lr: float):
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)


def model_and_policy(config: dict):
    """The program's model of the config and its sparsity policy."""
    from repro.core import policy as pol
    from repro.models.cnn import build_cnn

    model = build_cnn(config["net"], image_size=config["image_size"],
                      width=config["width"],
                      num_classes=config["num_classes"])
    return model, getattr(pol, config["policy"]).with_(
        kernel_impl=config["kernel_impl"])


def build_step(config: dict, mesh: Optional[jax.sharding.Mesh] = None
               ) -> Callable:
    """``step(params, images, labels) -> (new_params, loss)``, jitted; with
    a ``("data",)`` mesh, data parallel over it."""
    model, policy = model_and_policy(config)
    lr = config["lr"]

    if mesh is None:
        def step(params, images, labels):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, images, labels, policy))(params)
            return sgd(params, grads, lr), loss
        return jax.jit(step)

    from repro.sharding.spmd_step import make_spmd_grad_fn
    grad_fn = make_spmd_grad_fn(
        lambda p, batch: model.loss(p, batch[0], batch[1], policy), mesh)

    def step(params, images, labels):
        loss, grads = grad_fn(params, (images, labels))
        return sgd(params, grads, lr), loss
    return jax.jit(step)


def collective_counts() -> Dict[str, int]:
    """The program's trace-time counters of its gradient collectives
    (``collective:<path>``: one per leaf each time a step is traced)."""
    from repro.kernels import stats
    return {k: v for k, v in sorted(stats.counts().items())
            if k.startswith("collective:")}
