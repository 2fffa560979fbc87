"""The paper's five CNN benchmarks (VGG16, ResNet18, GoogLeNet, DenseNet121,
MobileNet) built on core.sparse_conv's relu_conv/conv units, plus the trace
capture used to drive the cost model — mirroring the paper's §5 methodology
(layer-wise activation/gradient traces from real framework training).

Models are expressed as layer graphs of a small IR (ConvNode etc.) so that
one definition yields (a) the runnable JAX forward/backward, (b) the
ConvSpec list for the cost model, and (c) per-layer trace hooks.  Spatial
sizes are configurable: ``image_size=224`` gives the paper's ImageNet
geometry (for cost-model shape fidelity), smaller sizes give CPU-friendly
smoke/training configs.  Only representative blocks of the big nets are
repeated in reduced variants, exactly like the paper reports representative
blocks (Inception-3b, ResNet block-2, Dense-block-1).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import DC, SparsityPolicy
from repro.core.sparse_conv import (
    conv as sconv, depthwise_conv, depthwise_relu_conv, relu_conv,
)
from repro.core.sparse_linear import matmul as smatmul
from repro.core.costmodel import ConvSpec
from repro.kernels import stats

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Layer IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ConvNode:
    name: str
    out_ch: int
    kernel: int
    stride: int = 1
    padding: str = "SAME"
    has_bn: bool = False
    relu_after: bool = True       # (BN+)ReLU after this conv
    depthwise: bool = False


@dataclasses.dataclass
class PoolNode:
    name: str
    kind: str                     # "max" | "avg"
    size: int = 2
    stride: int = 2


@dataclasses.dataclass
class Trace:
    """Per-conv-layer tensors captured during one training step."""
    name: str
    act_out: jnp.ndarray          # post-(BN+)ReLU output feature map (NHWC)
    grad_out: jnp.ndarray         # gradient at the same point (post-Hadamard)
    input_act: jnp.ndarray        # the conv's input (post-ReLU of producer)
    grad_in: jnp.ndarray          # gradient arriving at the conv's output


def resolved_out_ch(node: ConvNode, in_ch: int) -> int:
    """Depthwise output width follows the input; the IR leaves it 0 until a
    walk supplies the producer's channel count.  Pure — the IR is never
    mutated, so init / conv_specs / re-init in any order agree."""
    return in_ch if node.depthwise else node.out_ch


def conv_init(key, node: ConvNode, in_ch: int, dtype=jnp.float32) -> Params:
    k = node.kernel
    c = 1 if node.depthwise else in_ch
    out_ch = resolved_out_ch(node, in_ch)
    fan_in = k * k * c
    w = jax.random.normal(key, (k, k, c, out_ch), jnp.float32) \
        * (2.0 / fan_in) ** 0.5
    p: Params = {"w": w.astype(dtype)}
    if node.has_bn:
        p["bn_scale"] = jnp.ones((out_ch,), jnp.float32)
        p["bn_bias"] = jnp.zeros((out_ch,), jnp.float32)
    return p


def batchnorm(x: jnp.ndarray, scale, bias, eps=1e-5) -> jnp.ndarray:
    with stats.lifecycle_scope("norm", "bn"):
        mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
        var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def apply_conv(p: Params, x_pre: jnp.ndarray, node: ConvNode,
               policy: SparsityPolicy, input_is_relu: bool) -> jnp.ndarray:
    """x_pre is PRE-activation of the producer if input_is_relu (the fused
    relu_conv consumes it), else the raw input."""
    if node.depthwise:
        if p["w"].shape[2] != 1 or x_pre.shape[-1] != p["w"].shape[3]:
            # Defensive escape hatch for malformed group structure; counted
            # (and scope-tagged for the static analyzer) so audits can
            # assert the sparse path never loses a layer.
            stats.record("conv:dense_fallback")
            with stats.lifecycle_scope("fallback", "conv_dense"):
                x = jnp.maximum(x_pre, 0) if input_is_relu else x_pre
                y = jax.lax.conv_general_dilated(
                    x, p["w"], (node.stride, node.stride), node.padding,
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    feature_group_count=x.shape[-1])
        elif input_is_relu:
            # depthwise through the sparse unit: groups == C, fused encode —
            # the dw→pw chain keeps the pre-activation contract end to end.
            y = depthwise_relu_conv(x_pre, p["w"], node.stride, node.padding,
                                    policy)
        else:
            y = depthwise_conv(x_pre, p["w"], node.stride, node.padding,
                               policy)
    elif input_is_relu:
        y = relu_conv(x_pre, p["w"], node.stride, node.padding, policy)
    else:
        y = sconv(x_pre, p["w"], node.stride, node.padding, policy)
    if node.has_bn:
        y = batchnorm(y, p["bn_scale"], p["bn_bias"])
    return y


def apply_pool(x: jnp.ndarray, node: PoolNode) -> jnp.ndarray:
    if node.kind == "max":
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, node.size, node.size, 1),
            (1, node.stride, node.stride, 1), "SAME")
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, node.size, node.size, 1),
        (1, node.stride, node.stride, 1), "SAME")
    return s / (node.size * node.size)


# ---------------------------------------------------------------------------
# Network definitions (sequential IR with branch support for blocks)
# ---------------------------------------------------------------------------

def vgg16_layers(width: float = 1.0) -> List[Any]:
    def c(n, ch, **kw):
        return ConvNode(n, int(ch * width), 3, **kw)
    return [
        c("conv1", 64), c("conv2", 64), PoolNode("pool1", "max"),
        c("conv3", 128), c("conv4", 128), PoolNode("pool2", "max"),
        c("conv5", 256), c("conv6", 256), c("conv7", 256), PoolNode("pool3", "max"),
        c("conv8", 512), c("conv9", 512), c("conv10", 512), PoolNode("pool4", "max"),
        c("conv11", 512), c("conv12", 512), c("conv13", 512), PoolNode("pool5", "max"),
    ]


def mobilenet_layers(width: float = 1.0) -> List[Any]:
    """Linear dw/pw stack (paper evaluates the pw convs)."""
    out: List[Any] = [ConvNode("conv0", int(32 * width), 3, stride=2, has_bn=True)]
    chans = [64, 128, 128, 256, 256, 512, 512, 512, 512, 512, 512, 1024, 1024]
    strides = [1, 2, 1, 2, 1, 2, 1, 1, 1, 1, 1, 2, 1]
    for i, (ch, st) in enumerate(zip(chans, strides)):
        out.append(ConvNode(f"dw{i+1}", 0, 3, stride=st, has_bn=True,
                            depthwise=True))
        out.append(ConvNode(f"pw{i+1}", int(ch * width), 1, has_bn=True))
    return out


@dataclasses.dataclass
class Branch:
    name: str
    paths: List[List[Any]]        # parallel sub-sequences
    merge: str                    # "concat" | "add"


def googlenet_inception3b(width: float = 1.0) -> List[Any]:
    """Inception-3b (paper Fig. 3a): 4 parallel paths, concat merge, no BN."""
    w = lambda ch: int(ch * width)
    return [
        ConvNode("pre", w(192), 3, has_bn=False),
        PoolNode("pool1", "max"),
        Branch("incep3b", [
            [ConvNode("conv11", w(64), 1)],
            [ConvNode("conv33r", w(96), 1), ConvNode("conv33", w(128), 3)],
            [ConvNode("conv55r", w(16), 1), ConvNode("conv55", w(32), 5)],
            [PoolNode("bpool", "max", 3, 1), ConvNode("convpp", w(32), 1)],
        ], merge="concat"),
    ]


def resnet18_block2(width: float = 1.0) -> List[Any]:
    """Residual block-2 region (paper Fig. 13/14): BN nets → OUT-only in BP."""
    w = lambda ch: int(ch * width)
    return [
        ConvNode("stem", w(64), 3, stride=2, has_bn=True),
        Branch("res1", [
            [ConvNode("b1conv1", w(128), 3, stride=2, has_bn=True),
             ConvNode("b1conv2", w(128), 3, has_bn=True, relu_after=False)],
            [ConvNode("b1skip", w(128), 1, stride=2, has_bn=True,
                      relu_after=False)],
        ], merge="add"),
        Branch("res2", [
            [ConvNode("b2conv1", w(128), 3, has_bn=True),
             ConvNode("b2conv2", w(128), 3, has_bn=True, relu_after=False)],
            [],
        ], merge="add"),
    ]


def densenet_block1(width: float = 1.0, growth: int = 32, reps: int = 6) -> List[Any]:
    """Dense-block-1 (paper Fig. 12a): concat merges retain sparsity."""
    g = max(8, int(growth * width))
    out: List[Any] = [ConvNode("stem", int(64 * width), 3, stride=2, has_bn=True)]
    for i in range(reps):
        out.append(Branch(f"dense{i+1}", [
            [ConvNode(f"d{i+1}c1", 4 * g, 1, has_bn=True),
             ConvNode(f"d{i+1}c3", g, 3, has_bn=True)],
            [],
        ], merge="concat"))
    return out


NETWORKS: Dict[str, Callable[..., List[Any]]] = {
    "vgg16": vgg16_layers,
    "googlenet": googlenet_inception3b,
    "resnet18": resnet18_block2,
    "densenet121": densenet_block1,
    "mobilenet": mobilenet_layers,
}


# ---------------------------------------------------------------------------
# Build / run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CNNModel:
    name: str
    layers: List[Any]
    num_classes: int
    image_size: int
    in_ch: int = 3

    def init(self, key, dtype=jnp.float32) -> Params:
        params: Params = {}
        keys = iter(jax.random.split(key, 256))

        def walk(nodes, in_ch):
            for node in nodes:
                if isinstance(node, ConvNode):
                    params[node.name] = conv_init(next(keys), node, in_ch, dtype)
                    in_ch = resolved_out_ch(node, in_ch)
                elif isinstance(node, PoolNode):
                    pass
                elif isinstance(node, Branch):
                    outs = []
                    for path in node.paths:
                        outs.append(walk(path, in_ch))
                    in_ch = sum(outs) if node.merge == "concat" else outs[0]
            return in_ch

        final_ch = walk(self.layers, self.in_ch)
        params["head"] = {
            "w": (jax.random.normal(next(keys), (final_ch, self.num_classes),
                                    jnp.float32) * final_ch ** -0.5).astype(dtype)}
        return params

    def apply(self, params: Params, images: jnp.ndarray,
              policy: SparsityPolicy = DC,
              capture: Optional[Dict[str, jnp.ndarray]] = None) -> jnp.ndarray:
        """images: (N, H, W, C) → logits.  ``capture`` (if a dict) is filled
        with post-ReLU activations per conv layer name."""

        def run(nodes, x, input_is_relu):
            # x is raw input if not input_is_relu, else PRE-activation
            for node in nodes:
                if isinstance(node, ConvNode):
                    with stats.layer_scope(node.name):
                        x = apply_conv(params[node.name], x, node, policy,
                                       input_is_relu)
                    input_is_relu = node.relu_after
                    if capture is not None:
                        capture[node.name] = jnp.maximum(x, 0) \
                            if node.relu_after else x
                elif isinstance(node, PoolNode):
                    with stats.lifecycle_scope("pool", node.kind):
                        if input_is_relu:
                            x = jnp.maximum(x, 0)
                            input_is_relu = False
                        x = apply_pool(x, node)
                elif isinstance(node, Branch):
                    if input_is_relu:
                        with stats.lifecycle_scope("merge", node.merge):
                            x = jnp.maximum(x, 0)
                        input_is_relu = False
                    outs = []
                    for path in node.paths:
                        y, y_relu = run(path, x, False)
                        if y_relu:
                            with stats.lifecycle_scope("merge", node.merge):
                                y = jnp.maximum(y, 0)
                        outs.append(y)
                    with stats.lifecycle_scope("merge", node.merge):
                        x = jnp.concatenate(outs, -1) \
                            if node.merge == "concat" \
                            else functools.reduce(jnp.add, outs)
                    if node.merge == "add":
                        # post-merge ReLU (ResNet): re-enters pre-act domain
                        if capture is not None:
                            capture[node.name] = jnp.maximum(x, 0)
                        input_is_relu = True
            return x, input_is_relu

        x, is_relu = run(self.layers, images, False)
        with stats.lifecycle_scope("pool", "gap"):
            if is_relu:
                x = jnp.maximum(x, 0)
            x = jnp.mean(x, axis=(1, 2))         # global average pool
        # Head GEMM through the sparse-aware unit: the pooled feature (post
        # global-mean, so typically dense) contributes no FP skipping, but
        # its bitmap is computed once and threaded to the WG stage, and the
        # incoming logit gradient's masks are shared across both backward
        # GEMMs — same metadata contract as every conv layer.
        with stats.layer_scope("head"):
            return smatmul(x, params["head"]["w"], policy)

    def loss(self, params: Params, images, labels,
             policy: SparsityPolicy = DC) -> jnp.ndarray:
        logits = self.apply(params, images, policy)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    # -- cost-model bridge --
    def conv_specs(self, batch: int) -> List[ConvSpec]:
        """Static ConvSpec list at this model's geometry (input_is_relu /
        has_bn flags follow the graph, as the paper's applicability rules)."""
        specs: List[ConvSpec] = []

        def walk(nodes, in_ch, hw, input_is_relu):
            for node in nodes:
                if isinstance(node, ConvNode):
                    out_ch = resolved_out_ch(node, in_ch)
                    specs.append(ConvSpec(
                        name=node.name, c=in_ch, h=hw, w=hw, m=out_ch,
                        r=node.kernel, s=node.kernel, stride=node.stride,
                        groups=in_ch if node.depthwise else 1,
                        has_bn=node.has_bn, input_is_relu=input_is_relu,
                        output_feeds_relu=node.relu_after, batch=batch))
                    in_ch = out_ch
                    hw = -(-hw // node.stride)
                    input_is_relu = node.relu_after
                elif isinstance(node, PoolNode):
                    hw = -(-hw // node.stride)
                    input_is_relu = False
                elif isinstance(node, Branch):
                    outs = []
                    hws = []
                    for path in node.paths:
                        o, h2 = walk(path, in_ch, hw, False)
                        outs.append(o)
                        hws.append(h2)
                    in_ch = sum(outs) if node.merge == "concat" else outs[0]
                    hw = hws[0]
                    input_is_relu = node.merge == "add"
            return in_ch, hw

        walk(self.layers, self.in_ch, self.image_size, False)
        return specs

    def gemm_workload(self, batch: int) -> List[dict]:
        """Per-layer, per-stage GEMM requests this model's training step
        lowers onto the ``kernels.ops.sparse_gemm`` dispatcher: one row per
        (layer, stage ∈ {fp, bp_dx, wg}) with the per-group (M, K, N) dims
        and group count — the shardable workload description a ``GemmSpec``
        is resolved against (consumed by
        ``benchmarks/kernel_audit.launch_shape_audit``)."""
        rows: List[dict] = []
        for s in self.conv_specs(batch):
            g = s.groups
            t_out = batch * s.u * s.v            # output pixels (FP/WG rows)
            t_in = batch * s.h * s.w             # input pixels (dX rows)
            for stage, m, k, n in (
                    ("fp", t_out, s.crs, s.m // g),
                    ("bp_dx", t_in, s.mrs, s.c // g),
                    ("wg", s.crs, t_out, s.m // g)):
                # cin/cout let the consumer recompute the engine's channel
                # granularities (conv_channel_granularity needs the FULL
                # channel counts, not the per-group dims).
                rows.append({"layer": s.name, "stage": stage, "groups": g,
                             "m": m, "k": k, "n": n,
                             "cin": s.c, "cout": s.m})
        return rows


def build_cnn(name: str, *, image_size: int = 32, width: float = 1.0,
              num_classes: int = 100) -> CNNModel:
    import copy
    layers = copy.deepcopy(NETWORKS[name](width))
    return CNNModel(name=name, layers=layers, num_classes=num_classes,
                    image_size=image_size)
