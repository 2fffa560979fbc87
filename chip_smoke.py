#!/usr/bin/env python3
"""Smoke test of the sparse-backprop training path on a TPU.

Trains full-width VGG16 (224x224 inputs, 1000 classes, float32, random
weights from a seed) for a few steps through the paper's full sparse path
(policy IN_OUT_WR on the Pallas kernels), checks its gradients against the
plain XLA path (policy DC), and checks from the trace-time counters that
the Pallas GEMMs ran and nothing fell back to a dense or standalone-scan
path.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # data-parallel step on a 4-chip host

Every phase runs in this one process.  The last line of standard output
is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Without a TPU the script exits non-zero before building any model.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

NET, IMAGE, CLASSES = "vgg16", 224, 1000
STEPS = 3
LR = 0.01
SEED = 0          # weights and batches
# Pallas path vs the plain-XLA DC path, worst per-layer relative error
# ||g_pallas - g_dc|| / ||g_dc||, both computed at HIGHEST matmul precision
# (full f32 on the MXU; the kernels follow jax.default_matmul_precision).
# At the TPU's default f32 precision each MXU pass rounds its operands to
# bf16, and Mosaic and XLA round differently; over 13 chained convs with
# max pools, whose argmax flips at near-ties, that alone moves conv1's
# gradient by about 1e-1, so no tight check is possible there.  In full
# f32 the two paths differ only in summation order (im2col GEMM vs XLA
# conv): 5e-4 to 7e-4 at conv1 after the same amplification, 1e-7 at the
# last convs, on a v5e.  A dropped or doubled tile moves its layer's
# gradient by about its share of the sum: conv1's dW adds 3136 K-tiles at
# batch 16, one of them about sqrt(1/3136) ≈ 2e-2.
GRAD_TOL = 5e-3
# The program that trains runs at default precision, where the check above
# cannot be tight.  There the Pallas path is held, per layer, to a multiple
# of the rounding floor the same run measures: the distance of DC at
# default precision from DC at HIGHEST (0.127 at conv1 falling to 4e-3 at
# the head, batch 8 on a v5e; the Pallas path's distance was 0.94 to 1.07
# times DC's at every layer).  This catches gross faults that only the default
# lowering has; a single dropped tile hides under the floor here and is
# caught by GRAD_TOL above.  The floor never drops below GRAD_TOL, so a
# backend whose default is full f32 is not held to zero.
ROUNDING_MULT = 2.0
# SPMD vs single device: the same kernels at the same precision on both
# sides; only the batch reduction order differs (per-shard sums + psum).
SPMD_TOL = 1e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """The backend check, before any model code is imported or run."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's backend is {backend!r}",
              file=sys.stderr, flush=True)
        sys.exit(2)
    devices = jax.devices()
    if len(devices) < n_chips:
        fail(f"needs {n_chips} TPU chips, found {len(devices)}")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}")
    sys.path.insert(0, SRC)
    return devices[:n_chips]


def rel_errors(got, want) -> dict:
    """Per-layer relative norm error of two gradient pytrees."""
    import jax
    import numpy as np
    out = {}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        out[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
    return out


def report_errors(name: str, errs: dict, tol: float) -> None:
    for layer, e in errs.items():
        log(f"  {name} {layer}: rel_err={e!r}")
    layer, worst = max(errs.items(), key=lambda kv: kv[1])
    log(f"{name}: worst rel_err={worst!r} at {layer} (tolerance {tol!r})")
    if not worst <= tol:
        fail(f"{name}: gradient error {worst!r} at {layer} exceeds {tol!r}")


def check_counters(counts: dict, min_encodes: int) -> None:
    """The Pallas path ran, from the trace-time counters of one step."""
    sparse = sum(v for k, v in counts.items()
                 if k.startswith(("gemm:predicated:", "gemm:compact:")))
    dense = sum(v for k, v in counts.items() if k.startswith("gemm:dense:"))
    scans = sum(v for k, v in counts.items() if k.startswith("scan_pallas:"))
    checks = [
        ("gemm:predicated:* + gemm:compact:* > 0", sparse > 0),
        ("gemm:dense:* == 0", dense == 0),
        ("conv:dense_fallback == 0", counts.get("conv:dense_fallback", 0) == 0),
        ("scan_pallas:* == 0", scans == 0),
        (f"encode:act >= {min_encodes}",
         counts.get("encode:act", 0) >= min_encodes),
        ("emit:grad >= 1", counts.get("emit:grad", 0) >= 1),
    ]
    for name, ok in checks:
        log(f"  counter check {name}: {'ok' if ok else 'FAILED'}")
    bad = [name for name, ok in checks if not ok]
    if bad:
        fail(f"counter checks failed: {bad} (counts {counts})")


def build(batch: int):
    import jax
    from repro.core import policy as pol
    from repro.models.cnn import build_cnn
    model = build_cnn(NET, image_size=IMAGE, width=1.0, num_classes=CLASSES)
    params = model.init(jax.random.key(SEED))
    policy = pol.IN_OUT_WR.with_(kernel_impl="pallas")
    # Convs whose input is a ReLU pre-activation take the fused
    # relu_encode; the others (after the raw image or a pool) do not.
    encodes = sum(s.input_is_relu for s in model.conv_specs(batch))
    return model, params, policy, encodes


def one_chip(args) -> None:
    import jax
    import numpy as np
    from repro.core import policy as pol
    from repro.data.pipeline import image_batch
    from repro.kernels import stats

    model, params, policy, encodes = build(args.batch)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model {NET} width=1.0 image={IMAGE} classes={CLASSES} "
        f"params={n_params} batch={args.batch} policy=IN_OUT_WR/pallas")

    def step(params, img, labels):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, img, labels, policy))(params)
        new = jax.tree.map(lambda p, g: p - LR * g, params, grads)
        return new, loss, grads

    batches = [image_batch(SEED, i, batch=args.batch, image_size=IMAGE,
                           num_classes=CLASSES) for i in range(STEPS)]
    jax.block_until_ready(batches)

    stats.reset()
    t0 = time.perf_counter()
    lowered = jax.jit(step).lower(params, *batches[0])
    counts = stats.counts()
    compiled = lowered.compile()
    log(f"compile_s={time.perf_counter() - t0!r}")
    ma = compiled.memory_analysis()
    log(f"compiled memory: argument={ma.argument_size_in_bytes} "
        f"output={ma.output_size_in_bytes} temp={ma.temp_size_in_bytes} "
        f"code={ma.generated_code_size_in_bytes}")
    log(f"counters (one traced step): {json.dumps(counts, sort_keys=True)}")
    check_counters(counts, encodes)

    p, grads0, losses = params, None, []
    for i, (img, labels) in enumerate(batches):
        t0 = time.perf_counter()
        p, loss, grads = compiled(p, img, labels)
        jax.block_until_ready((p, loss, grads))
        dt = time.perf_counter() - t0
        losses.append(float(loss))
        log(f"step {i}: loss={losses[-1]!r} step_s={dt!r}")
        if i == 0:
            grads0 = grads
    if not all(math.isfinite(v) for v in losses):
        fail(f"non-finite loss: {losses}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_limit={mem.get('bytes_limit')}")

    # Correctness: gradients at the initial params on batch 0, Pallas path
    # against the plain-XLA DC path, both in full f32; then the trained
    # (default-precision) program against DC's own rounding at that
    # precision.
    def grads_fn(policy):
        return jax.jit(jax.value_and_grad(
            lambda p, img, labels: model.loss(p, img, labels, policy)))
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = grads_fn(pol.DC)(params, *batches[0])
        hi_loss, hi_grads = grads_fn(policy)(params, *batches[0])
    dc_loss, dc_grads = grads_fn(pol.DC)(params, *batches[0])
    log(f"loss (step 0) default precision pallas={losses[0]!r} "
        f"dc={float(dc_loss)!r}; highest precision "
        f"pallas={float(hi_loss)!r} dc={float(ref_loss)!r}")
    for g in (grads0, hi_grads):
        if not all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g)):
            fail("non-finite Pallas gradients")
    report_errors("pallas_vs_dc", rel_errors(hi_grads, ref_grads), GRAD_TOL)

    floor = rel_errors(dc_grads, ref_grads)
    errs = rel_errors(grads0, ref_grads)
    bounds = {layer: ROUNDING_MULT * max(f, GRAD_TOL)
              for layer, f in floor.items()}
    for layer, e in errs.items():
        log(f"  default-precision {layer}: pallas rel_err={e!r} "
            f"dc rel_err={floor[layer]!r} bound={bounds[layer]!r}")
    layer = max(errs, key=lambda k: errs[k] / bounds[k])
    log(f"default-precision pallas vs highest-precision dc: worst "
        f"rel_err={errs[layer]!r} at {layer} (bound {bounds[layer]!r})")
    bad = [layer for layer, e in errs.items() if not e <= bounds[layer]]
    if bad:
        fail(f"default-precision gradients beyond {ROUNDING_MULT!r} x the "
             f"DC rounding floor at {bad}")


def four_chips(args) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data.pipeline import image_batch
    from repro.kernels import stats
    from repro.sharding.spmd_step import make_spmd_grad_fn

    n = 4
    model, params, policy, _ = build(args.batch)
    global_batch = n * args.batch
    log(f"model {NET} width=1.0 image={IMAGE} classes={CLASSES} "
        f"per_chip_batch={args.batch} global_batch={global_batch} "
        f"policy=IN_OUT_WR/pallas")
    batch = image_batch(SEED, 0, batch=global_batch, image_size=IMAGE,
                        num_classes=CLASSES)

    def loss_fn(p, b):
        return model.loss(p, b[0], b[1], policy)

    mesh = jax.make_mesh((n,), ("data",))
    params_r = jax.device_put(params, NamedSharding(mesh, P()))
    batch_s = jax.device_put(batch, NamedSharding(mesh, P("data")))
    shards = batch_s[0].addressable_shards
    log(f"batch shards: {[(str(s.device), s.data.shape) for s in shards]}")
    if len(batch_s[0].sharding.device_set) != n or any(
            s.data.shape[0] != args.batch for s in shards):
        fail("the batch is not split across the 4 devices")

    spmd = make_spmd_grad_fn(loss_fn, mesh)
    stats.reset()
    t0 = time.perf_counter()
    loss_s, grads_s = spmd(params_r, batch_s)
    jax.block_until_ready((loss_s, grads_s))
    log(f"spmd compile+first_step_s={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    loss_s, grads_s = spmd(params_r, batch_s)
    jax.block_until_ready((loss_s, grads_s))
    log(f"spmd step_s={time.perf_counter() - t0!r}")
    counts = stats.counts()
    log("collective counters: " + json.dumps(
        {k: v for k, v in sorted(counts.items())
         if k.startswith("collective:")}))
    in_use = [(str(d), (d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in jax.devices()[:n]]
    log(f"bytes_in_use per device: {in_use}")
    if not all(b > 0 for _, b in in_use):
        fail("a device holds no data")

    dev0 = jax.devices()[0]
    single = jax.jit(jax.value_and_grad(loss_fn))
    loss_1, grads_1 = single(jax.device_put(params, dev0),
                             jax.device_put(batch, dev0))
    log(f"loss spmd={float(loss_s)!r} single={float(loss_1)!r}")
    if not math.isfinite(float(loss_s)):
        fail(f"non-finite SPMD loss {float(loss_s)!r}")
    loss_err = abs(float(loss_s) - float(loss_1)) / abs(float(loss_1))
    log(f"spmd_vs_single loss rel_err={loss_err!r}")
    if not loss_err <= SPMD_TOL:
        fail(f"SPMD loss differs from single device by {loss_err!r}")
    report_errors("spmd_vs_single", rel_errors(grads_s, grads_1), SPMD_TOL)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-parallel phase")
    ap.add_argument("--batch", type=int, default=None,
                    help="per-chip batch (default 16 on one chip, "
                         "4 per chip with --four-chips)")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    if args.batch is None:
        # One chip: batch 8 takes under half of the 16 GB HBM (compiled
        # footprint about 7 GB), so 16.  Four chips: 4 per chip, so that
        # the single-device reference at the global batch fits on one chip.
        args.batch = 4 if args.four_chips else 16
    devices = require_tpu(n_chips)

    from repro.launch.cache import use_compile_cache
    log(f"device {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {use_compile_cache()}")
    (four_chips if args.four_chips else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
