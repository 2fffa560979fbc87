"""The paper's central claim, as an executable property: sparse backprop is
EXACT — custom-VJP (with output/input skipping) == dense autodiff."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy as pol
from repro.core.sparse_conv import conv as sconv, relu_conv
from repro.core.sparse_linear import act_matmul, matmul as smm, relu_matmul

POLICIES = [
    pol.DC,
    pol.IN.with_(kernel_impl="pallas", block=(16, 16, 16)),
    pol.IN_OUT.with_(kernel_impl="pallas", block=(16, 16, 16)),
    pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 16, 8)),
    pol.IN_OUT,  # xla_ref
]


def _rand(shape, key, sparsify=0.0):
    rng = np.random.default_rng(key)
    x = rng.standard_normal(shape).astype(np.float32)
    if sparsify:
        x *= rng.random(shape) > sparsify
    return jnp.asarray(x)


@pytest.mark.parametrize("policy", POLICIES)
def test_relu_matmul_vjp_exact(policy):
    x = _rand((37, 29), 0)
    w = _rand((29, 23), 1)
    ct = _rand((37, 23), 2)
    y, vjp = jax.vjp(lambda x, w: relu_matmul(x, w, policy), x, w)
    yd, vjpd = jax.vjp(lambda x, w: jnp.maximum(x, 0) @ w, x, w)
    np.testing.assert_allclose(y, yd, rtol=1e-4, atol=1e-4)
    for g, gd in zip(vjp(ct), vjpd(ct)):
        np.testing.assert_allclose(g, gd, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("policy", [pol.DC, pol.IN_OUT.with_(
    kernel_impl="pallas", block=(16, 16, 16))])
def test_relu2_matmul_vjp_exact(policy):
    """Squared-ReLU (transformer-FFN variant): same zero footprint."""
    x = _rand((24, 18), 3)
    w = _rand((18, 20), 4)
    ct = _rand((24, 20), 5)
    f = lambda x, w: act_matmul(x, w, policy, "relu2")
    g = lambda x, w: jnp.square(jnp.maximum(x, 0)) @ w
    y, vjp = jax.vjp(f, x, w)
    yd, vjpd = jax.vjp(g, x, w)
    np.testing.assert_allclose(y, yd, rtol=1e-4, atol=1e-4)
    for a, b in zip(vjp(ct), vjpd(ct)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# (channels, block): C = 5 on square tiles; C = 3 and 64 give a patch K
# (27, 576) that is not a whole number of bk = 128 tiles, C = 128 one that is.
CONV_CHANNELS = [(5, (16, 16, 16)), (3, (16, 128, 16)), (64, (16, 128, 16)),
                 (128, (16, 128, 16))]


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID"), (2, "VALID")])
@pytest.mark.parametrize("c,block", CONV_CHANNELS)
def test_relu_conv_vjp_exact(stride, padding, c, block):
    policy = pol.IN_OUT.with_(kernel_impl="pallas", block=block)
    x = _rand((2, 9, 11, c), 6)
    w = _rand((3, 3, c, 7), 7)

    def dense(x, w):
        return jax.lax.conv_general_dilated(
            jnp.maximum(x, 0), w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    f = lambda x, w: (relu_conv(x, w, stride, padding, policy) ** 2).sum()
    g = lambda x, w: (dense(x, w) ** 2).sum()
    np.testing.assert_allclose(f(x, w), g(x, w), rtol=1e-4)
    ga, gb = jax.grad(f, (0, 1))(x, w), jax.grad(g, (0, 1))(x, w)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


def test_bn_between_conv_and_relu_keeps_output_sparsity_exact():
    """The paper's headline case (Fig. 3c): BN after the conv — input
    sparsity is gone but output-sparse backprop is still exact."""
    policy = pol.IN_OUT.with_(kernel_impl="pallas", block=(8, 8, 8))
    x = _rand((2, 8, 8, 4), 8)
    w = _rand((3, 3, 4, 6), 9)
    scale = jnp.ones((6,))
    bias = jnp.zeros((6,))

    def bn(y):
        mu = y.mean(axis=(0, 1, 2), keepdims=True)
        var = y.var(axis=(0, 1, 2), keepdims=True)
        return (y - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    def net_sparse(x, w, w2):
        h = sconv(x, w, 1, "SAME", policy)       # conv (input not relu'd)
        h = bn(h)
        # h is now the PRE-activation consumed by the fused relu-conv
        return (relu_conv(h, w2, 1, "SAME", policy) ** 2).sum()

    def net_dense(x, w, w2):
        h = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = bn(h)
        h = jnp.maximum(h, 0)
        y = jax.lax.conv_general_dilated(
            h, w2, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return (y ** 2).sum()

    w2 = _rand((3, 3, 6, 5), 10)
    np.testing.assert_allclose(net_sparse(x, w, w2), net_dense(x, w, w2),
                               rtol=1e-4)
    gs = jax.grad(net_sparse, (0, 1, 2))(x, w, w2)
    gd = jax.grad(net_dense, (0, 1, 2))(x, w, w2)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


def test_gradients_flow_through_chain_of_units():
    """Three stacked relu_matmul units (the CONV-ReLU-CONV chain of Fig. 5)."""
    policy = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))
    sizes = [12, 16, 16, 8]
    ws = [_rand((sizes[i], sizes[i + 1]), 20 + i) for i in range(3)]
    x = _rand((10, 12), 30)

    def net(ws, impl):
        h = x @ ws[0]
        for w in ws[1:]:
            h = impl(h, w)
        return (h ** 2).sum()

    f = lambda ws: net(ws, lambda h, w: relu_matmul(h, w, policy))
    g = lambda ws: net(ws, lambda h, w: jnp.maximum(h, 0) @ w)
    np.testing.assert_allclose(f(ws), g(ws), rtol=1e-4)
    for a, b in zip(jax.grad(f)(ws), jax.grad(g)(ws)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)
