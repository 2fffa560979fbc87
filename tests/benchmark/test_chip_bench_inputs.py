"""The chip benchmark's yardstick on the CPU: traffic, FLOP tables, names."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import flops, imagegen, layers, spec  # noqa: E402

BENCHMARK = spec.load_benchmark(ROOT)
CONFIGS = [c["name"] for c in BENCHMARK["configs"]]


def _config(name):
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_letterbox_is_zero_outside_the_content_and_seeded():
    traffic = _traffic("letterbox")
    f = jax.jit(imagegen.batch_fn(traffic, batch=24, image_size=64,
                                  channels=3, num_classes=10))
    key = imagegen.seed_key(2**31 + 12345)
    img, labels = f(key)
    img2, labels2 = f(key)
    np.testing.assert_array_equal(np.asarray(img), np.asarray(img2))
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(labels2))
    other, _ = f(imagegen.seed_key(12345))
    assert not np.array_equal(np.asarray(img), np.asarray(other))

    boxes = imagegen.content_boxes(traffic["aspect_ratios"], 64)
    img = np.asarray(img)
    for x in img:
        rows = np.nonzero(np.any(x != 0, axis=(1, 2)))[0]
        cols = np.nonzero(np.any(x != 0, axis=(0, 2)))[0]
        box = (rows[0], cols[0], rows[-1] - rows[0] + 1, cols[-1] - cols[0] + 1)
        assert box in {tuple(b) for b in boxes}, box
        top, left, h, w = box
        outside = np.ones(x.shape[:2], bool)
        outside[top:top + h, left:left + w] = False
        assert np.all(x[outside] == 0)
        assert np.all(x[top:top + h, left:left + w] != 0)
        assert abs(x[top:top + h, left:left + w].mean()) < 1e-5


def test_content_boxes_at_224():
    boxes = imagegen.content_boxes(_traffic("letterbox")["aspect_ratios"], 224)
    # A 16:9 frame at the top: 126 rows of content, 98 blank rows below.
    assert [tuple(b) for b in boxes] == [(0, 0, 126, 224)]
    # 3:4 portrait: 168 columns of content, 56 blank columns right of it.
    assert tuple(imagegen.content_boxes([[3, 4]], 224)[0]) == (0, 0, 224, 168)
    with pytest.raises(ValueError):
        imagegen.content_boxes([[0, 3]], 224)
    assert [tuple(b) for b in imagegen.content_boxes([[1, 1]], 224)] == [
        (0, 0, 224, 224)]


def test_letterbox_sharded_draws_the_letterbox_frames():
    """The four-chip cell's mix differs from ``letterbox`` only in its
    name and why, so a seed draws the same pool for both."""
    drawn = ("aspect_ratios", "noise_std", "pool_batches")
    sharded, plain = _traffic("letterbox_sharded"), _traffic("letterbox")
    assert set(sharded) == set(plain)
    assert {k: sharded[k] for k in drawn} == {k: plain[k] for k in drawn}
    key = imagegen.seed_key(2**31 + 777)
    a, b = (imagegen.batch_fn(t, batch=4, image_size=32, channels=3,
                              num_classes=10)(key) for t in (sharded, plain))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fullframe_fills_the_frame():
    f = imagegen.batch_fn(_traffic("fullframe"), batch=4, image_size=32,
                          channels=3, num_classes=10)
    img, _ = f(imagegen.seed_key(3))
    assert np.all(np.asarray(img) != 0)
    np.testing.assert_allclose(np.asarray(img).mean(axis=(1, 2, 3)), 0,
                               atol=1e-5)


def test_zero_tile_fraction():
    x = jnp.zeros((2, 16, 16, 8)).at[:, 8:].set(1.0)
    # 512 pixel rows in 4 tiles of 128: rows 0-127 of each image are zero.
    assert float(imagegen.zero_tile_fraction(x)) == 0.5
    assert float(imagegen.zero_tile_fraction(jnp.ones((1, 4, 4, 3)))) == 0.0


@pytest.mark.parametrize("name", CONFIGS)
def test_layer_table_matches_the_program(name):
    from repro.models.cnn import build_cnn
    cfg = _config(name)
    model = build_cnn(cfg["net"], image_size=cfg["image_size"],
                      width=cfg["width"], num_classes=cfg["num_classes"])
    specs = model.conv_specs(cfg["batch_per_chip"])
    table = list(layers.iter_convs(cfg["layers"]))
    got = [(n["name"], n["in_ch"], n["in_hw"], n["out_ch"], n["kernel"],
            n["stride"], n["bn"], n["relu"], layers.conv_out_hw(n))
           for n in table]
    want = [(s.name, s.c, s.h, s.m, s.r, s.stride, s.has_bn,
             s.output_feeds_relu, s.u) for s in specs]
    assert got == want
    dense = sum(2 * s.u * s.v * s.r * s.s * s.c * s.m * (2 if i == 0 else 3)
                for i, s in enumerate(specs))
    head = 2 * layers.final_channels(cfg["layers"]) * cfg["num_classes"] * 3
    assert flops.train_flops_per_image(cfg) == dense + head

    ref = spec.load_module(os.path.join(BENCH, "reference",
                                        cfg["reference"] + ".py"), "ref")
    shapes = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.key(0))
    want_shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.map(lambda s: s.shape, shapes) == jax.tree.map(
        lambda s: s.shape, want_shapes)


def test_benchmark_names_and_units():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = b["end_to_end"] + b["per_layer"]
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [m["name"] for m in metrics])
    assert len(names) == len(set(names))
    for c in b["configs"]:
        assert all(name.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in b["workloads"]:
        assert name.match(w["config"]) and name.match(w["traffic"])
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    assert all(name.match(n) for n in names)
    assert all(unit.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_run_without_a_tpu_fails_and_prints_no_result():
    workload = BENCHMARK["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
