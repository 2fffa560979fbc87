"""Plain reference of the CNNs the configs describe, in float32 at the
matmul precision the config states (``matmul_precision``: ``default`` is
JAX's default, one bfloat16 pass per float32 product on a TPU's MXU with
float32 accumulation; ``highest`` is full float32).

Written from a config's ``layers`` table alone: ``lax.conv_general_dilated``
with SAME padding, ``reduce_window`` max pools, training-mode BatchNorm over
the batch given, residual adds, global average pooling, one linear head and
the mean softmax cross-entropy.  It imports nothing of the program under
test.  The parameter layout ({layer: {"w", "bn_scale", "bn_bias"}, "head":
{"w"}}) is the one the program takes, so both start from the same weights.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.layers import final_channels, iter_convs

BN_EPS = 1e-5


def init_params(config: dict, key) -> Dict[str, Any]:
    """He-normal conv weights (HWIO), BN scale 1 and bias 0, head weights
    N(0, 1/fan_in), all float32; layer i draws from fold_in(key, i)."""
    params: Dict[str, Any] = {}
    convs = list(iter_convs(config["layers"]))
    for i, node in enumerate(convs):
        k, c, m = node["kernel"], node["in_ch"], node["out_ch"]
        w = jax.random.normal(jax.random.fold_in(key, i), (k, k, c, m),
                              jnp.float32) * (2.0 / (k * k * c)) ** 0.5
        p = {"w": w}
        if node["bn"]:
            p["bn_scale"] = jnp.ones((m,), jnp.float32)
            p["bn_bias"] = jnp.zeros((m,), jnp.float32)
        params[node["name"]] = p
    f = final_channels(config["layers"])
    params["head"] = {"w": jax.random.normal(
        jax.random.fold_in(key, len(convs)), (f, config["num_classes"]),
        jnp.float32) * f ** -0.5}
    return params


PRECISIONS = {"default": lax.Precision.DEFAULT,
              "highest": lax.Precision.HIGHEST}


def _conv(x, p, node, dtype, precision):
    s = node["stride"]
    # The dense reference oracle, not an escape from the sparse engine.
    # repro-lint: allow(CONV_FALLBACK)
    y = lax.conv_general_dilated(
        x, p["w"].astype(dtype), (s, s), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)
    if node["bn"]:
        mu = jnp.mean(y, axis=(0, 1, 2), keepdims=True)
        var = jnp.mean(jnp.square(y - mu), axis=(0, 1, 2), keepdims=True)
        y = (y - mu) * lax.rsqrt(var + BN_EPS) * p["bn_scale"].astype(dtype) \
            + p["bn_bias"].astype(dtype)
    return jnp.maximum(y, 0) if node["relu"] else y


def _run(layers, x, params, dtype, precision):
    for node in layers:
        op = node["op"]
        if op == "conv":
            x = _conv(x, params[node["name"]], node, dtype, precision)
        elif op == "pool":
            if node["kind"] != "max":
                raise ValueError(f"unknown pool kind {node['kind']!r}")
            k, s = node["size"], node["stride"]
            x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max,
                                  (1, k, k, 1), (1, s, s, 1), "SAME")
        elif op == "branch":
            if node["merge"] != "add":
                raise ValueError(f"unknown merge {node['merge']!r}")
            outs = [_run(path, x, params, dtype, precision)
                    for path in node["paths"]]
            x = sum(outs[1:], outs[0])
            if node["relu"]:
                x = jnp.maximum(x, 0)
        else:
            raise ValueError(f"unknown layer op {op!r}")
    return x


def logits(params, images, config: dict, dtype=jnp.float32, precision=None):
    precision = PRECISIONS[precision or config["matmul_precision"]]
    x = _run(config["layers"], images.astype(dtype), params, dtype,
             precision)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["head"]["w"].astype(dtype), precision=precision)


def loss(params, images, labels, config: dict, dtype=jnp.float32,
         precision=None):
    """Mean softmax cross-entropy, every step computed in ``dtype``, at
    ``precision`` (a key of ``PRECISIONS``; the config's by default)."""
    logp = jax.nn.log_softmax(logits(params, images, config, dtype,
                                      precision))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)
                     ).astype(jnp.float32)


def conv_inputs(params, images, config: dict) -> Dict[str, jax.Array]:
    """The input each conv sees, by name (post-ReLU, post-pool)."""
    seen: Dict[str, jax.Array] = {}

    def run(layers, x):
        for node in layers:
            if node["op"] == "conv":
                seen[node["name"]] = x
                x = _conv(x, params[node["name"]], node, jnp.float32,
                          lax.Precision.HIGHEST)
            elif node["op"] == "branch":
                outs = [run(path, x) for path in node["paths"]]
                x = sum(outs[1:], outs[0])
                x = jnp.maximum(x, 0) if node["relu"] else x
            else:
                x = _run([node], x, params, jnp.float32,
                         lax.Precision.HIGHEST)
        return x

    run(config["layers"], images)
    return seen
