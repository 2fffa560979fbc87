"""How ``correct`` is decided for a training cell.

Set-up drives the compiled step through its first three steps from the
seed's weights, on pool batches 0, 1 and 2.  The plain reference
(``reference/<name>.py``, float32 at the matmul precision the config
states) follows the same three steps from the same weights.  As the
program splits a batch over its chips, each reference step takes its loss
and gradient as the mean over chunks of ``batch_per_chip`` images, each
chunk computed alone: with one chip the whole batch, and in a model with
BatchNorm the statistics of each chip's own images.  Five numbers are
compared, each with a limit of its own (``limits/<workload>.json``):

  loss_step1..3  |program loss - reference loss| / |reference loss| at
                 each of the three steps;
  grad_norm      per leaf, the gap between the norm of the first gradient
                 as the optimizer got it, (p0 - p1) / lr, and the
                 reference's, over the larger of the reference's norm of
                 that leaf and of the median leaf; the worst leaf;
  update_norm    the same for the change p3 - p0 after the three steps.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf numbers.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_step1", "loss_step2", "loss_step3", "grad_norm",
           "update_norm")
STEPS = 3
NEGLIGIBLE_LEAF = 1e-3


def _flat(tree) -> Dict[str, np.ndarray]:
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def states_readings(p0, p1, p3, losses: Sequence[float], lr: float) -> dict:
    """Norms of what the optimizer got and did, from three states."""
    f0, f1, f3 = _flat(p0), _flat(p1), _flat(p3)
    return {"losses": [float(x) for x in losses],
            "grad": {k: float(np.linalg.norm((f0[k] - f1[k]) / lr))
                     for k in f0},
            "update": {k: float(np.linalg.norm(f3[k] - f0[k])) for k in f0}}


def chunked_value_and_grad(loss_fn: Callable, rows: int) -> Callable:
    """``f(params, images, labels) -> (loss, grads)``: the mean over
    consecutive chunks of ``rows`` images (the whole batch where it has no
    more) of ``value_and_grad(loss_fn)``, one jitted call per chunk."""
    vg = jax.jit(jax.value_and_grad(loss_fn))

    def f(params, images, labels):
        n = images.shape[0]
        if n <= rows:
            return vg(params, jnp.asarray(images), jnp.asarray(labels))
        if n % rows:
            raise ValueError(f"a batch of {n} does not split into chunks "
                             f"of {rows}")
        parts = [vg(params, jnp.asarray(images[i:i + rows]),
                    jnp.asarray(labels[i:i + rows]))
                 for i in range(0, n, rows)]
        return jax.tree.map(lambda *xs: sum(xs) / len(xs), *parts)
    return f


def reference_readings(loss_fn: Callable, params0, batches: List,
                       lr: float, rows: int) -> dict:
    """Three SGD steps of ``loss_fn(params, images, labels)`` from
    ``params0`` over ``batches`` (host or device arrays), each step's
    loss and gradient the mean over chunks of ``rows`` images."""
    vg = chunked_value_and_grad(loss_fn, rows)
    p, losses, first = params0, [], None
    for images, labels in batches[:STEPS]:
        loss, grads = vg(p, images, labels)
        if first is None:
            first = grads
        p = jax.tree.map(lambda a, g: a - lr * g, p, grads)
        losses.append(float(loss))
    f0, f3 = _flat(params0), _flat(p)
    return {"losses": losses,
            "grad": {k: float(np.linalg.norm(v))
                     for k, v in _flat(first).items()},
            "update": {k: float(np.linalg.norm(f3[k] - f0[k])) for k in f0}}


def _leaf_gap(got: Dict[str, float], want: Dict[str, float],
              keep: Sequence[str]) -> float:
    if not keep:
        return float("nan")
    floor = float(np.median([want[k] for k in keep]))
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in keep)


def compare(got: dict, want: dict) -> Dict[str, float]:
    """The five numbers of ``got`` (program or control) against ``want``
    (the reference)."""
    out = {f"loss_step{i + 1}": abs(g - w) / abs(w)
           for i, (g, w) in enumerate(zip(got["losses"], want["losses"]))}
    med = float(np.median(list(want["grad"].values())))
    keep = [k for k, v in want["grad"].items() if v >= NEGLIGIBLE_LEAF * med]
    out["grad_norm"] = _leaf_gap(got["grad"], want["grad"], keep)
    out["update_norm"] = _leaf_gap(got["update"], want["update"], keep)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number present, finite and within its limit."""
    return all(name in numbers and np.isfinite(numbers[name])
               and numbers[name] <= limits[name] for name in limits)
