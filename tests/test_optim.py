"""Optimizer, loss scaling, and gradient compression tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.compression import (compressed_psum, dequantize,
                                     init_error_state, quantize)
from repro.optim.optimizer import OptConfig, adamw_init, adamw_update, cosine_lr


def test_adamw_converges_quadratic():
    cfg = OptConfig(learning_rate=0.1, warmup_steps=5, total_steps=200,
                    weight_decay=0.0, grad_clip=10.0)
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = adamw_init(params)
    loss_fn = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(200):
        g = jax.grad(loss_fn)(params)
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(loss_fn(params)) < 1e-3


def test_nonfinite_grad_skips_update():
    cfg = OptConfig()
    params = {"w": jnp.ones(4)}
    state = adamw_init(params)
    bad = {"w": jnp.full(4, jnp.nan)}
    p2, s2, m = adamw_update(bad, state, params, cfg)
    np.testing.assert_array_equal(p2["w"], params["w"])
    assert int(s2["step"]) == 0
    assert float(m["skipped"]) == 1.0


def test_loss_scale_unscales():
    cfg = OptConfig(loss_scale=1024.0, weight_decay=0.0)
    params = {"w": jnp.ones(2)}
    s0 = adamw_init(params)
    g_scaled = {"w": jnp.asarray([1024.0, 2048.0])}
    _, _, m1 = adamw_update(g_scaled, s0, params, cfg)
    cfg2 = OptConfig(loss_scale=0.0, weight_decay=0.0)
    _, _, m2 = adamw_update({"w": jnp.asarray([1.0, 2.0])},
                            adamw_init(params), params, cfg2)
    np.testing.assert_allclose(m1["grad_norm"], m2["grad_norm"], rtol=1e-6)


def test_cosine_schedule_shape():
    cfg = OptConfig(learning_rate=1.0, warmup_steps=10, total_steps=100,
                    min_lr_frac=0.1)
    assert float(cosine_lr(jnp.asarray(0), cfg)) == 0.0
    assert abs(float(cosine_lr(jnp.asarray(10), cfg)) - 1.0) < 1e-6
    assert float(cosine_lr(jnp.asarray(100), cfg)) <= 0.1 + 1e-6


def test_master_weights_preserve_precision():
    """bf16 params with f32 master: tiny updates are not lost."""
    cfg = OptConfig(learning_rate=1e-4, weight_decay=0.0, warmup_steps=0,
                    total_steps=10_000, min_lr_frac=1.0)
    params = {"w": jnp.full((4,), 256.0, jnp.bfloat16)}   # ulp = 1.0 at 256
    state = adamw_init(params)
    for _ in range(50):
        g = {"w": jnp.full((4,), 1.0, jnp.bfloat16)}
        params, state, _ = adamw_update(g, state, params, cfg)
    # master moved by ~50·1e-4 even though each step < bf16 ulp
    assert float(state["master"]["w"][0]) < 256.0 - 3e-3


def test_compressed_psum_shard_map():
    """Mechanics of the int8 EF all-reduce under shard_map (axis size 1 on
    CPU; numerics of quantize path still exercised end-to-end)."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g = {"w": jnp.asarray([0.1, -0.5, 0.25, 3.0])}
    err = init_error_state(g)

    def f(g, err):
        return compressed_psum(g, err, "data")

    out, err2 = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                              out_specs=(P(), P()))(g, err)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    np.testing.assert_allclose(out["w"], g["w"], atol=scale + 1e-7)
    # error feedback holds the residual
    np.testing.assert_allclose(np.asarray(err2["w"]),
                               np.asarray(g["w"] - out["w"]), atol=1e-6)


def test_compression_roundtrip_precision():
    g = jnp.linspace(-1, 1, 255)
    q, scale, err = quantize(g, jnp.zeros_like(g))
    back = dequantize(q, scale)
    assert float(jnp.max(jnp.abs(back - g))) <= float(scale) / 2 + 1e-7
