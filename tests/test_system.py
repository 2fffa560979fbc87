"""End-to-end behaviour tests for the paper's system."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.configs.base import TrainConfig
from repro.core.policy import DC, IN_OUT_WR
from repro.data.pipeline import image_batch
from repro.launch.train import train_loop
from repro.models.cnn import build_cnn

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py fails, and prints no result line, when JAX finds no
    TPU: a chip run never falls back to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


_CACHE_FLAGS = ("jax_compilation_cache_dir",
                "jax_compilation_cache_include_metadata_in_key",
                "jax_hlo_source_file_canonicalization_regex",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_flags():
    """Restores the persistent cache's flags, and its state, after a test
    that sets them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = {k: getattr(jax.config, k) for k in _CACHE_FLAGS}
    try:
        yield
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_compile_cache_dir_follows_env(monkeypatch, cache_flags):
    """The entry points' cache helper leaves a JAX_COMPILATION_CACHE_DIR
    to JAX and otherwise uses the repository's fixed .jax_cache."""
    from repro.launch import cache
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, "/elsewhere/cache")
    assert cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv(cache.ENV_VAR)
    want = os.path.join(os.path.abspath(REPO), ".jax_cache")
    assert cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_keeps_each_programs_scope_names(
        monkeypatch, tmp_path, cache_flags):
    """Two programs that differ only in a scope name do not share a cache
    entry once the helper has run: each compiled program reports its own
    op_name, which a profile of it shows and the chip benchmark reads."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch import cache
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    cache.use_compile_cache()
    # What JAX does with the variable when it starts.
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2
        return f

    texts = [jax.jit(scoped(name)).lower(jnp.ones(4)).compile().as_text()
             for name in ("scope_first", "scope_second")]
    assert "scope_first" in texts[0]
    assert "scope_second" in texts[1] and "scope_first" not in texts[1]
    assert len([p for p in os.listdir(tmp_path) if "jit_f" in p]) == 2


def test_lm_training_learns():
    """examples-style LM training descends on the synthetic stream."""
    cfg = SMOKE_ARCHS["smollm-360m"]
    tcfg = TrainConfig(total_steps=90, learning_rate=5e-3, warmup_steps=5)
    out = train_loop(cfg, tcfg, batch_size=8, seq_len=32, steps=90,
                     log_every=0)
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first - 0.3, (first, last)


def test_cnn_training_with_paper_technique_end_to_end():
    """Sparse-backprop CNN training: learns, and the trace-driven cost
    model reports a BP speedup for the run's own sparsity."""
    from repro.core import costmodel as cm
    from repro.core.sparsity import element_sparsity
    model = build_cnn("vgg16", image_size=8, width=0.25, num_classes=10)
    params = model.init(jax.random.key(0))
    policy = IN_OUT_WR.with_(kernel_impl="xla_ref")
    # Fixed batch (memorization smoke): the tiny reduced-geometry model has
    # ~1e-3 gradients, so a fresh batch per step just random-walks the loss
    # around ln(10) — descent is only a deterministic property of repeated
    # steps on one batch.
    img, labels = image_batch(0, 0, batch=4, image_size=8, num_classes=10)
    losses = []
    for step in range(5):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, img, labels, policy))(params)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        losses.append(float(loss))
    assert losses[-1] < losses[0]

    cap = {}
    model.apply(params, img, capture=cap)
    specs = model.conv_specs(batch=4)
    traces = []
    for s in specs:
        act = cap.get(s.name)
        dens = 1.0 - float(element_sparsity(act)) if act is not None else 1.0
        traces.append(cm.LayerTrace(x_density=dens, g_in_density=dens,
                                    out_mask_density=dens))
    dc = cm.network_cost(specs, traces, "DC")
    sp = cm.network_cost(specs, traces, "IN_OUT_WR")
    assert sp["bp_cycles"] < dc["bp_cycles"]
    assert sp["total_cycles"] < dc["total_cycles"]


@pytest.mark.slow
def test_dryrun_smoke_cell_subprocess():
    """The 512-device dry-run machinery works end-to-end (subprocess so the
    forced device count never leaks into this test session)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "stablelm-1.6b", "--shape", "train_4k", "--mesh", "multi",
         "--smoke", "--out", "/tmp/test_dryrun_cell.jsonl"],
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "[ok]" in out.stdout


def test_grad_compression_training_parity():
    """int8 EF compression barely perturbs a short optimization run."""
    from repro.optim.compression import init_error_state, quantize, dequantize
    from repro.optim.optimizer import OptConfig, adamw_init, adamw_update
    cfg = OptConfig(learning_rate=0.05, warmup_steps=0, total_steps=100,
                    weight_decay=0.0)
    target = jnp.asarray(np.random.default_rng(0).standard_normal(16),
                         jnp.float32)

    def run(compressed):
        params = {"w": jnp.zeros(16)}
        state = adamw_init(params)
        err = init_error_state(params)
        for _ in range(60):
            g = jax.grad(lambda p: jnp.sum((p["w"] - target) ** 2))(params)
            if compressed:
                q, scale, err["w"] = quantize(g["w"], err["w"])
                g = {"w": dequantize(q, scale)}
            params, state, _ = adamw_update(g, state, params, cfg)
        return float(jnp.sum((params["w"] - target) ** 2))

    exact, comp = run(False), run(True)
    assert comp < exact + 0.05
