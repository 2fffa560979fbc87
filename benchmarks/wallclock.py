"""Wall-clock truth harness — measured time, honestly bounded.

Every number this module emits follows the same methodology:

  1. the measured callable is jitted and called ``warmup`` times first, so
     trace + compile time is EXCLUDED from every reported figure (the
     ``us_total`` column of benchmarks/run.py deliberately includes it;
     this file is the per-call complement);
  2. every timed call is fenced with ``jax.block_until_ready`` — async
     dispatch means an unfenced ``time.perf_counter`` pair measures queue
     submission, not execution (the same bug class as the per-step
     ``float(metrics["loss"])`` sync that launch/train.py used to have);
  3. the reported figure is the MEDIAN of ``reps`` fenced calls with the
     inter-quartile range as spread — never a single sample, never a mean
     that one scheduler hiccup can poison.

Tables (one CSV each under benchmarks/results/, all rows in BENCH_7.json):

  * ``gemm``        — one ``kernels.ops.sparse_gemm`` dispatch per schedule
                      ∈ {predicated, compact, dense} for one CNN-derived
                      workload (dims from ``CNNModel.gemm_workload``) and
                      one FFN workload (the backward dX GEMM the paper's
                      output sparsity targets);
  * ``train_step``  — one whole jitted train step of models/cnn.py and
                      models/ffn.py (forward + backward + SGD update);
  * ``autotune``    — the decision log of a scripted autotune session
                      (``autotune_session``): sparse→dense drift retunes
                      plus per-(spec, shape) keyed selections, every row
                      traceable to its measured live fraction.

``BENCH_7.json`` at the repo root is schema-stable: ``check_schema``
validates the exact key set per table and the acceptance coverage (every
schedule measured for ≥1 CNN and ≥1 FFN workload); CI runs the smoke
geometry and fails on drift.  See docs/benchmarking.md.

``BENCH_8.json`` is the fused-emit evidence (PR 8): one ``emit`` table
comparing, per backward-dX workload × pallas schedule, the SAME GEMM run
three ways — ``plain`` (no bitmap), ``fused`` (σ′ + ``bitmap_emit`` staged
in the epilogue, one launch returning ``(out, bits)``), and ``gemm_scan``
(σ′ GEMM then a standalone ``kernels.bitmap_scan`` over the output — the
pre-PR-8 two-launch pipeline).  ``check_emit_schema`` validates the key
set, the coverage, and — on full-geometry documents, i.e. the committed
artifact — the headline claim: fused strictly beats GEMM-then-scan on
every (workload, schedule) cell.

``BENCH_9.json`` is the sparsity-on-the-wire evidence (PR 10): one
``collective`` table comparing, per mesh shape × live fraction, the SAME
block-sparse gradient all-reduced two ways inside a ``shard_map`` body —
``dense_psum`` (every block on the wire) and ``bitmap`` (the
``sharding.collectives.sparse_psum`` compressed reduce: psum the tiny
block bitmap, gather/psum only union-live blocks into a static
``ceil(cutoff·nblocks)`` buffer, runtime dense fallback past the
cutoff).  The per-shard block masks are CORRELATED (the same pattern on
every shard) — the dW regime the collective exists for; uncorrelated
masks union to ~dense and honestly take the fallback.
``check_collective_schema`` validates the key set and coverage, and —
on full-geometry documents — the headline claim: bitmap beats dense at
the lowest live fraction on every mesh, and past the cutoff (where the
runtime fallback engages) never loses more than the bitmap-psum
overhead allowance.  BENCH_9 generation is opt-in (``--collective-out``)
so BENCH_7/8-only invocations cannot clobber the committed artifact.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(REPO_ROOT, "BENCH_7.json")
BENCH8_PATH = os.path.join(REPO_ROOT, "BENCH_8.json")
BENCH9_PATH = os.path.join(REPO_ROOT, "BENCH_9.json")

SCHEMA_VERSION = 1
SCHEDULES = ("predicated", "compact", "dense")
EMIT_SCHEDULES = ("predicated", "compact")   # the pallas emit-capable pair
EMIT_VARIANTS = ("plain", "fused", "gemm_scan")
COLLECTIVE_VARIANTS = ("dense_psum", "bitmap")
COLLECTIVE_LIVE_FRACS = (0.05, 0.1, 0.25, 1.0)
# The bench cutoff is deliberately tight (capacity = 1/8 of the blocks):
# on a shared-memory CPU "mesh" the wire IS the memory bus, so the
# compressed path's local gather/scatter copies cost the same per byte as
# the psum they save — compression only wins when capacity + overhead
# stays well under the dense volume.  A real interconnect (wire ≫ memory)
# widens the win and would justify the looser training default
# (``sharding.spmd_step.DEFAULT_CUTOFF``).
COLLECTIVE_CUTOFF = 0.125
# Fallback rows (live_frac > cutoff) may not beat dense — they ARE dense
# plus a tiny bitmap psum + branch; allow that overhead, bounded.
COLLECTIVE_FALLBACK_SLACK = 1.25

# The exact per-table row key sets the BENCH files commit to.  The schema
# checkers fail on ANY deviation — added keys are drift just like missing.
ROW_KEYS = {
    "gemm": ("table", "workload", "schedule", "m", "k", "n", "groups",
             "block", "us_median", "us_iqr", "reps", "warmup"),
    "train_step": ("table", "workload", "schedule", "batch", "params",
                   "us_median", "us_iqr", "reps", "warmup"),
    "emit": ("table", "workload", "schedule", "variant", "m", "k", "n",
             "groups", "block", "emit_gran", "us_median", "us_iqr",
             "reps", "warmup"),
    "collective": ("table", "mesh", "devices", "m", "n", "block",
                   "live_frac", "cutoff", "variant", "us_median", "us_iqr",
                   "reps", "warmup"),
}
AUTOTUNE_LOG_KEYS = ("seq", "event", "key", "shape", "groups", "schedule",
                     "block", "live_frac", "operand_frac", "samples")


# ---------------------------------------------------------------------------
# The one timing primitive
# ---------------------------------------------------------------------------

def measure(call: Callable[[], object], *, warmup: int = 2,
            reps: int = 5) -> Dict[str, float]:
    """Median-of-``reps`` fenced wall time of ``call`` in µs, compile
    excluded.

    ``call`` must return its device output (a jitted function application):
    the first of the ``warmup`` calls traces and compiles; every call —
    warmup and timed alike — is fenced with ``jax.block_until_ready`` so a
    timed interval can never start while a previous dispatch is still in
    flight, and never end before its own work has."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(call())
    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append((time.perf_counter() - t0) * 1e6)
    times.sort()
    q1 = times[len(times) // 4]
    q3 = times[min(len(times) - 1, (3 * len(times)) // 4)]
    return {
        "us_median": round(statistics.median(times), 2),
        "us_iqr": round(q3 - q1, 2),
        "reps": int(reps),
        "warmup": int(warmup),
    }


# ---------------------------------------------------------------------------
# Workload synthesis — block-structured sparsity with a KNOWN live fraction
# ---------------------------------------------------------------------------

def _blocky(key, shape: Tuple[int, int], block2: Tuple[int, int],
            live: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(data, block bitmap): iid normal data gated by a Bernoulli(``live``)
    BLOCK mask.  Element-iid zeros almost never kill a whole tile, so the
    block bitmap of such data is ~all-live; gating whole blocks makes the
    measured live fraction equal the drawn bitmap's mean — the workload's
    sparsity is known, not hoped for."""
    from repro.kernels.shapes import ceil_to
    m, n = shape
    b0, b1 = block2
    mb, nb = ceil_to(m, b0) // b0, ceil_to(n, b1) // b1
    kb, kd = jax.random.split(key)
    bm = jax.random.bernoulli(kb, live, (mb, nb))
    expand = jnp.repeat(jnp.repeat(bm, b0, 0), b1, 1)[:m, :n]
    data = jax.random.normal(kd, shape, jnp.float32) * expand
    return data, bm


def cnn_gemm_dims(*, image_size: int, width: float, batch: int,
                  layer: str = "conv2", stage: str = "bp_dx",
                  net: str = "vgg16") -> Tuple[str, Tuple[int, int, int]]:
    """One (M, K, N) from the CNN's OWN workload description — the dims a
    real training step hands the dispatcher, not invented round numbers."""
    from repro.models.cnn import build_cnn
    model = build_cnn(net, image_size=image_size, width=width,
                      num_classes=10)
    for row in model.gemm_workload(batch):
        if row["layer"] == layer and row["stage"] == stage:
            name = f"cnn:{net}:{layer}:{stage}"
            return name, (row["m"], row["k"], row["n"])
    raise KeyError(f"{layer}/{stage} not in {net} workload")


def bench_gemm_rows(*, smoke: bool) -> List[dict]:
    """One measured row per schedule × workload.  All three schedules run
    the SAME operands and masks; predicated/compact go through the Pallas
    kernels, dense is the xla_ref lowering — so the comparison is the
    paper's §5 scenario sweep at one fixed GEMM."""
    from repro.core import policy as pol
    from repro.kernels import ops
    from repro.kernels.shapes import block_bitmap

    block = (8, 8, 8)
    timing = dict(warmup=1, reps=3) if smoke else dict(warmup=2, reps=5)
    geo = dict(image_size=8, width=0.125, batch=2) if smoke else \
        dict(image_size=10, width=0.25, batch=2)

    cnn_name, cnn_dims = cnn_gemm_dims(**geo)
    ffn_tokens = 64 if smoke else 128
    workloads = [
        (cnn_name, cnn_dims),
        # the down-projection's backward dX GEMM: dL/dh = g @ W_downᵀ with
        # the hidden ReLU mask killing output tiles (paper's core GEMM)
        ("ffn:relu_bwd_dx", (ffn_tokens, 32, 64)),
    ]
    schedule_policies = {
        "predicated": pol.IN_OUT.with_(kernel_impl="pallas", block=block),
        "compact": pol.IN_OUT_WR.with_(kernel_impl="pallas", block=block),
        "dense": pol.IN_OUT,                       # xla_ref ⇒ "dense"
    }

    rows: List[dict] = []
    for wname, (m, k, n) in workloads:
        key = jax.random.key(hash(wname) % (2 ** 31))
        ka, kb_, ko = jax.random.split(key, 3)
        a, _ = _blocky(ka, (m, k), (block[0], block[1]), live=0.6)
        b = jax.random.normal(kb_, (k, n), jnp.float32)
        out_t, _ = _blocky(ko, (m, n), (block[0], block[2]), live=0.5)
        for sched, policy in schedule_policies.items():
            spec = policy.gemm_spec()
            assert spec.schedule == sched, (spec.schedule, sched)
            masks = ops.GemmMasks(
                out=block_bitmap(out_t, spec.block[0], spec.block[2]),
                a=block_bitmap(a, spec.block[0], spec.block[1]),
                b=None)

            fn = jax.jit(functools.partial(
                lambda a_, b_, masks_, spec_: ops.sparse_gemm(
                    a_, b_, masks_, spec_), spec_=spec))
            rows.append({
                "table": "gemm", "workload": wname, "schedule": sched,
                "m": m, "k": k, "n": n, "groups": spec.groups,
                "block": "x".join(map(str, spec.block)),
                **measure(lambda: fn(a, b, masks), **timing),
            })
    return rows


# ---------------------------------------------------------------------------
# Fused bitmap emission vs GEMM-then-scan (the BENCH_8 evidence)
# ---------------------------------------------------------------------------

def bench_emit_rows(*, smoke: bool) -> List[dict]:
    """One measured row per workload × pallas schedule × variant.

    The workload is the paper's hot GEMM — backward dX (``dy @ Wᵀ``) with
    the σ′ mask killing output tiles — and the variants are the same GEMM
    run three ways on identical operands and masks:

      * ``plain``      σ′ epilogue only (no bitmap anywhere) — the floor;
      * ``fused``      σ′ + ``bitmap_emit`` staged in the epilogue: ONE
                       launch returns ``(out, bits)``, thresholding each
                       accumulator tile at writeback;
      * ``gemm_scan``  σ′ GEMM, then a standalone ``kernels.bitmap_scan``
                       re-reads the output — the pre-PR-8 pipeline this
                       epilogue deletes from the training hot path.

    The committed (full-geometry) BENCH_8.json must show fused < gemm_scan
    on every cell (``check_emit_schema`` enforces it).

    Workload choice: the structural advantage of the emit epilogue is that
    it runs only on LIVE output tiles inside the producing launch, while
    the standalone scan re-reads EVERY tile of the output — so the honest
    showcase is the paper's sparse-dy regime (25% live σ′ tiles) on
    backward-dX geometries whose output is large relative to the reduction
    axis: a MobileNet pointwise conv's dX (K = Cout of a 1×1 kernel) and
    an FFN down-projection's dX.  The compact schedule is bounded to the
    drawn live-tile count (the WDU capacity a trained step would carry)."""
    import numpy as np

    from repro.core import policy as pol
    from repro.kernels import ops
    from repro.kernels.shapes import block_bitmap

    block = (8, 32, 8)
    emit_gran = (block[0], block[2])
    live = 0.25
    timing = dict(warmup=1, reps=3) if smoke else dict(warmup=2, reps=9)
    geo = dict(image_size=32, width=0.5, batch=2 if smoke else 8,
               layer="pw1", net="mobilenet")

    cnn_name, cnn_dims = cnn_gemm_dims(**geo)
    ffn_tokens = 256 if smoke else 1024
    workloads = [
        (cnn_name, cnn_dims),
        # the down-projection's backward dX GEMM: dL/dh = g @ W_downᵀ with
        # the hidden ReLU mask killing output tiles (paper's core GEMM)
        ("ffn:relu_bwd_dx", (ffn_tokens, 32, 64)),
    ]
    schedule_policies = {
        "predicated": pol.IN_OUT.with_(kernel_impl="pallas", block=block),
        "compact": pol.IN_OUT_WR.with_(kernel_impl="pallas", block=block),
    }

    rows: List[dict] = []
    for wname, (m, k, n) in workloads:
        key = jax.random.key(hash(("emit", wname)) % (2 ** 31))
        ka, kb_, km = jax.random.split(key, 3)
        dy = jax.random.normal(ka, (m, k), jnp.float32)
        wt = jax.random.normal(kb_, (k, n), jnp.float32)
        # σ′ footprint: block-structured so the out mask has dead tiles
        _, mult_bm = _blocky(km, (m, n), (block[0], block[2]), live)
        mult = jnp.repeat(jnp.repeat(mult_bm, block[0], 0),
                          block[2], 1)[:m, :n].astype(jnp.float32)
        n_live = int(np.asarray(mult_bm).sum())
        for sched, policy in schedule_policies.items():
            base = policy.gemm_spec()
            assert base.schedule == sched, (base.schedule, sched)
            if sched == "compact":
                base = base.with_(max_active_blocks=n_live)
            masks = ops.GemmMasks(out=block_bitmap(mult, block[0], block[2]))
            spec_p = base.with_(epilogue=("sigma_prime",))
            spec_f = base.with_(epilogue=("sigma_prime", "bitmap_emit"),
                                emit_gran=emit_gran)

            def plain(a_, b_, masks_, mult_):
                return ops.sparse_gemm(a_, b_, masks_, spec_p,
                                       epilogue_mult=mult_)

            def fused(a_, b_, masks_, mult_):
                return ops.sparse_gemm(a_, b_, masks_, spec_f,
                                       epilogue_mult=mult_)

            def gemm_scan(a_, b_, masks_, mult_):
                out = ops.sparse_gemm(a_, b_, masks_, spec_p,
                                      epilogue_mult=mult_)
                return out, ops.bitmap_scan(out, block=emit_gran,
                                            kind="grad")

            for variant, fn in (("plain", plain), ("fused", fused),
                                ("gemm_scan", gemm_scan)):
                jfn = jax.jit(fn)
                rows.append({
                    "table": "emit", "workload": wname, "schedule": sched,
                    "variant": variant, "m": m, "k": k, "n": n,
                    "groups": base.groups,
                    "block": "x".join(map(str, block)),
                    "emit_gran": "x".join(map(str, emit_gran)),
                    **measure(lambda: jfn(dy, wt, masks, mult), **timing),
                })
    return rows


# ---------------------------------------------------------------------------
# Bitmap-compressed all-reduce vs dense psum (the BENCH_9 evidence)
# ---------------------------------------------------------------------------

def _collective_meshes() -> List[Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    """Mesh shapes the collective table sweeps, derived from the devices
    actually visible: always the flat data mesh, plus a 2-D (data, pod)
    factoring when the device count supports it — the compressed reduce
    must not regress when the psum spans more than one mesh axis."""
    n_dev = jax.device_count()
    meshes = [((n_dev,), ("data",))]
    if n_dev >= 4 and n_dev % 2 == 0:
        meshes.append(((2, n_dev // 2), ("data", "pod")))
    return meshes


def bench_collective_rows(*, smoke: bool) -> List[dict]:
    """One measured row per mesh × live fraction × variant.

    Both variants all-reduce the SAME (devices, M, N) block-sparse
    gradient stack inside a jitted ``shard_map`` body; ``dense_psum`` is
    the uncompressed baseline, ``bitmap`` is ``sparse_psum`` fed the
    shard-local block bitmap (gran == wire block, so no coarsening is
    timed — the lifecycle already owns derivation).

    Workload construction is the honest part:

      * the live blocks are drawn ONCE per (mesh, live) cell and repeated
        on every shard — dW gradients in data-parallel training share
        sparsity structure across shards (same weights, same σ′
        geometry), and that correlation is what keeps the union small
        (uncorrelated shard masks union to ~dense and take the fallback);
      * the sparsity is ROW-BLOCK structured and the wire block spans the
        full row — the paper's regime: a feature whose activation the
        ReLU killed across the whole batch zeroes the entire dW row, so
        whole row-blocks go dead together.  Full-width wire blocks also
        keep the compact gather/scatter contiguous (each block one
        memcpy), which on a shared-memory CPU mesh is the difference
        between compression winning and drowning in strided-gather cost;
      * the live count is exact (a permutation draw, not a Bernoulli
        hope), so ``live_frac`` in each row is the workload's true wire
        live fraction and the cutoff comparison is sharp:
        ``live_frac ≤ cutoff`` rows exercise the compressed path,
        ``live_frac > cutoff`` rows the runtime dense fallback.

    ``sparse_psum`` is fed the FINE (gran-level) bitmap and told the
    wire block, so the timed path includes the gran→wire coarsening the
    lifecycle mandates (derivation, never a rescan)."""
    from repro.kernels import stats

    # The fallback/compressed runtime counters are host callbacks — per
    # execution, per shard.  They are audit instrumentation, not the
    # collective; staged into a timed trace they'd dominate the medians.
    prev_counting = stats.set_runtime_counting(False)
    try:
        return _collective_rows_inner(smoke=smoke)
    finally:
        stats.set_runtime_counting(prev_counting)


def _collective_rows_inner(*, smoke: bool) -> List[dict]:
    import numpy as np

    from jax.sharding import PartitionSpec as P

    from repro.sharding.collectives import dense_psum, sparse_psum

    n_dev = jax.device_count()
    b0 = 32 if smoke else 128            # row-block height; wire = (b0, N)
    m, n = (512, 256) if smoke else (8192, 2048)
    gran = (b0, b0)                      # the fine bitmap's granularity
    timing = dict(warmup=1, reps=3) if smoke else dict(warmup=2, reps=7)
    mt, nt_g = m // b0, n // b0          # fine-bitmap grid; wire nblk = mt

    rows: List[dict] = []
    for shape, names in _collective_meshes():
        mesh = jax.make_mesh(shape, names)
        spec_in = P(tuple(names))       # dim 0 sharded over every axis
        for live in COLLECTIVE_LIVE_FRACS:
            rng = np.random.default_rng(hash((shape, live)) % (2 ** 31))
            count = max(1, min(mt, round(live * mt)))
            row_live = np.zeros(mt, np.int32)
            row_live[rng.permutation(mt)[:count]] = 1
            expand = np.repeat(row_live, b0).astype(np.float32)[:, None]
            data = (rng.standard_normal((n_dev, m, n)).astype(np.float32)
                    * expand[None])
            bm = np.repeat(row_live[:, None], nt_g, 1)
            xs = jnp.asarray(data)
            bs = jnp.asarray(np.broadcast_to(bm, (n_dev, mt, nt_g)).copy())

            def body_dense(x, b):
                return dense_psum(x[0], axis_name=names)

            def body_bitmap(x, b):
                return sparse_psum(x[0], b[0], gran, axis_name=names,
                                   block=(b0, n),
                                   cutoff=COLLECTIVE_CUTOFF)

            for variant, body in (("dense_psum", body_dense),
                                  ("bitmap", body_bitmap)):
                fn = jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=(spec_in, spec_in),
                    out_specs=P(), check_vma=False))
                rows.append({
                    "table": "collective",
                    "mesh": "x".join(map(str, shape)),
                    "devices": n_dev, "m": m, "n": n,
                    "block": f"{b0}x{n}",
                    "live_frac": live, "cutoff": COLLECTIVE_CUTOFF,
                    "variant": variant,
                    **measure(lambda: fn(xs, bs), **timing),
                })
            del data, xs, bs
    return rows


# ---------------------------------------------------------------------------
# Whole train steps
# ---------------------------------------------------------------------------

def _tree_size(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def bench_train_rows(*, smoke: bool) -> List[dict]:
    from repro.core import policy as pol
    from repro.models.cnn import build_cnn
    from repro.models.ffn import FFNConfig, ffn_apply, ffn_init

    timing = dict(warmup=1, reps=3) if smoke else dict(warmup=2, reps=5)
    rows: List[dict] = []
    policy = pol.IN_OUT                           # xla_ref: CPU-feasible

    # -- CNN step --------------------------------------------------------
    batch = 2
    model = build_cnn("vgg16", image_size=8, width=0.125, num_classes=10)
    params = model.init(jax.random.key(0))
    img = jax.random.normal(jax.random.key(1), (batch, 8, 8, 3), jnp.float32)
    lbl = jax.random.randint(jax.random.key(2), (batch,), 0, 10)

    @jax.jit
    def cnn_step(p, img, lbl):
        loss, g = jax.value_and_grad(
            lambda q: model.loss(q, img, lbl, policy))(p)
        return jax.tree.map(lambda w, dw: w - 0.05 * dw, p, g), loss

    rows.append({
        "table": "train_step", "workload": "cnn:vgg16",
        "schedule": policy.gemm_spec().schedule, "batch": batch,
        "params": _tree_size(params),
        **measure(lambda: cnn_step(params, img, lbl), **timing),
    })

    # -- FFN step --------------------------------------------------------
    tokens = 32 if smoke else 64
    cfg = FFNConfig(d_model=16, d_ff=32, activation="relu",
                    sparse_policy=policy)
    fparams = ffn_init(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (tokens, cfg.d_model))
    y = jax.random.normal(jax.random.key(5), (tokens, cfg.d_model))

    @jax.jit
    def ffn_step(p, x, y):
        def loss(q):
            return jnp.mean((ffn_apply(q, x, cfg) - y) ** 2)
        l, g = jax.value_and_grad(loss)(p)
        return jax.tree.map(lambda w, dw: w - 0.05 * dw, p, g), l

    rows.append({
        "table": "train_step", "workload": "ffn:relu",
        "schedule": policy.gemm_spec().schedule, "batch": tokens,
        "params": _tree_size(fparams),
        **measure(lambda: ffn_step(fparams, x, y), **timing),
    })
    return rows


# ---------------------------------------------------------------------------
# Scripted autotune session — the traceability evidence
# ---------------------------------------------------------------------------

def autotune_session(*, drift_steps: Tuple[int, int] = (8, 10),
                     shape_steps: int = 6, seed: int = 0
                     ) -> Tuple[List[dict], List[dict], Dict[str, int]]:
    """Two-part eager session against a FRESH autotune cache; returns
    (per-step selections, decision log, cache counters).

    Part 1 (temporal drift, shapeless key): dispatch at ~25% live output
    tiles — the cache should settle on "compact" — then at 100% live,
    driving a drift retune through "predicated" to "dense" once the
    trailing window is all-dense.

    Part 2 (per-shape keys): two interleaved dims-keyed workloads, one
    staying sparse and one fully dense, must hold DIFFERENT schedules
    simultaneously — the per-(spec, shape) selection the key exists for.

    Eager dispatches only: masks are concrete, so every resolution reads
    MEASURED live fractions recorded by the dispatcher itself."""
    from repro.core import policy as pol
    from repro.kernels import autotune, ops, stats
    from repro.kernels.shapes import block_bitmap

    stats.reset()
    cache = autotune.reset(window=6, min_samples=3)
    block = (8, 8, 8)
    policy = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=block,
                                 autotune=True)
    selections: List[dict] = []
    step = 0

    def dispatch(live: float, dims: Optional[Tuple[int, int, int]],
                 phase: str) -> None:
        nonlocal step
        m, k, n = dims or (32, 16, 24)
        key = jax.random.key(seed * 10_000 + step)
        ka, kb_, ko = jax.random.split(key, 3)
        spec = policy.gemm_spec(dims=dims) if dims is not None \
            else policy.gemm_spec()
        a = jax.random.normal(ka, (m, k), jnp.float32)
        b = jax.random.normal(kb_, (k, n), jnp.float32)
        out_t, _ = _blocky(ko, (m, n), (spec.block[0], spec.block[2]), live)
        masks = ops.GemmMasks(
            out=block_bitmap(out_t, spec.block[0], spec.block[2]))
        ops.sparse_gemm(a, b, masks, spec)        # eager: concrete masks
        selections.append({"step": step, "phase": phase,
                           "live": live, "dims": dims,
                           "schedule": spec.schedule})
        step += 1

    sparse_steps, dense_steps = drift_steps
    for _ in range(sparse_steps):
        dispatch(0.25, None, "drift:sparse")
    for _ in range(dense_steps):
        dispatch(1.0, None, "drift:dense")
    for _ in range(shape_steps):
        dispatch(0.25, (32, 16, 24), "shape:A")
        dispatch(1.0, (16, 16, 16), "shape:B")

    counters = {"hits": cache.hits, "misses": cache.misses,
                "retunes": cache.retunes}
    return selections, autotune.log_rows(), counters


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------

def check_schema(doc: dict) -> List[str]:
    """Validate a BENCH_7 document; returns a list of problems (empty ⇒
    OK).  Checks the exact per-table key sets, the acceptance coverage
    (every schedule measured for ≥1 CNN and ≥1 FFN GEMM workload; a CNN
    and an FFN train step), positive fenced medians, and that every
    autotune log row carries its traceability fields."""
    errs: List[str] = []
    for top in ("schema_version", "bench", "jax_backend", "geometry",
                "rows", "autotune"):
        if top not in doc:
            errs.append(f"missing top-level key {top!r}")
    if errs:
        return errs
    if doc["schema_version"] != SCHEMA_VERSION:
        errs.append(f"schema_version {doc['schema_version']} != "
                    f"{SCHEMA_VERSION}")

    seen: Dict[str, set] = {"cnn": set(), "ffn": set()}
    train_seen = set()
    for i, row in enumerate(doc["rows"]):
        table = row.get("table")
        if table not in ROW_KEYS:
            errs.append(f"rows[{i}]: unknown table {table!r}")
            continue
        want = set(ROW_KEYS[table])
        got = set(row)
        if got != want:
            errs.append(f"rows[{i}] ({table}): key drift "
                        f"+{sorted(got - want)} -{sorted(want - got)}")
            continue
        if not (isinstance(row["us_median"], (int, float))
                and row["us_median"] > 0):
            errs.append(f"rows[{i}] ({table}): non-positive us_median")
        if table == "gemm":
            if row["schedule"] not in SCHEDULES:
                errs.append(f"rows[{i}]: unknown schedule "
                            f"{row['schedule']!r}")
            fam = row["workload"].split(":", 1)[0]
            if fam in seen:
                seen[fam].add(row["schedule"])
        elif table == "train_step":
            train_seen.add(row["workload"].split(":", 1)[0])

    for fam, scheds in seen.items():
        missing = set(SCHEDULES) - scheds
        if missing:
            errs.append(f"gemm coverage: {fam} workload missing schedules "
                        f"{sorted(missing)}")
    for fam in ("cnn", "ffn"):
        if fam not in train_seen:
            errs.append(f"train_step coverage: no {fam} row")

    at = doc["autotune"]
    for k in ("counters", "selections", "log"):
        if k not in at:
            errs.append(f"autotune: missing {k!r}")
    for i, row in enumerate(at.get("log", [])):
        if set(row) != set(AUTOTUNE_LOG_KEYS):
            errs.append(f"autotune.log[{i}]: key drift {sorted(row)}")
            break
    if not at.get("log"):
        errs.append("autotune.log is empty — selections are not traceable")
    return errs


def check_emit_schema(doc: dict) -> List[str]:
    """Validate a BENCH_8 document; returns a list of problems (empty ⇒
    OK).  Checks the exact ``emit`` row key set, the coverage (every
    variant measured for both pallas schedules on ≥1 CNN and ≥1 FFN
    backward-dX workload), positive fenced medians, AND — on
    full-geometry documents (the committed artifact) — the headline
    claim: fused σ′+emit strictly beats GEMM-then-scan on every cell.
    Smoke documents skip only the claim: reduced reps on shared CI
    runners make a strict wall-clock inequality a coin-flip; the
    committed full-geometry run is the evidence the PR stands on."""
    errs: List[str] = []
    for top in ("schema_version", "bench", "jax_backend", "geometry",
                "rows"):
        if top not in doc:
            errs.append(f"missing top-level key {top!r}")
    if errs:
        return errs
    if doc["schema_version"] != SCHEMA_VERSION:
        errs.append(f"schema_version {doc['schema_version']} != "
                    f"{SCHEMA_VERSION}")
    if doc["bench"] != "BENCH_8":
        errs.append(f"bench {doc['bench']!r} != 'BENCH_8'")

    want = set(ROW_KEYS["emit"])
    cells: Dict[Tuple[str, str], Dict[str, float]] = {}
    seen: Dict[str, set] = {"cnn": set(), "ffn": set()}
    for i, row in enumerate(doc["rows"]):
        if row.get("table") != "emit":
            errs.append(f"rows[{i}]: unknown table {row.get('table')!r}")
            continue
        got = set(row)
        if got != want:
            errs.append(f"rows[{i}] (emit): key drift "
                        f"+{sorted(got - want)} -{sorted(want - got)}")
            continue
        if row["schedule"] not in EMIT_SCHEDULES:
            errs.append(f"rows[{i}]: unknown schedule {row['schedule']!r}")
        if row["variant"] not in EMIT_VARIANTS:
            errs.append(f"rows[{i}]: unknown variant {row['variant']!r}")
            continue
        if not (isinstance(row["us_median"], (int, float))
                and row["us_median"] > 0):
            errs.append(f"rows[{i}] (emit): non-positive us_median")
            continue
        fam = row["workload"].split(":", 1)[0]
        if fam in seen:
            seen[fam].add((row["schedule"], row["variant"]))
        cells.setdefault((row["workload"], row["schedule"]), {})[
            row["variant"]] = row["us_median"]

    full = {(s, v) for s in EMIT_SCHEDULES for v in EMIT_VARIANTS}
    for fam, got in seen.items():
        missing = sorted(full - got)
        if missing:
            errs.append(f"emit coverage: {fam} workload missing cells "
                        f"{missing}")

    if doc.get("geometry") != "full":
        return errs                       # claim gated on committed runs
    for (wname, sched), by_variant in sorted(cells.items()):
        if set(by_variant) != set(EMIT_VARIANTS):
            continue                      # coverage error already reported
        if not by_variant["fused"] < by_variant["gemm_scan"]:
            errs.append(
                f"claim: fused ({by_variant['fused']}us) not faster than "
                f"gemm_scan ({by_variant['gemm_scan']}us) on "
                f"{wname}/{sched} — the emit epilogue must beat the "
                f"two-launch pipeline")
    return errs


def check_collective_schema(doc: dict) -> List[str]:
    """Validate a BENCH_9 document; returns a list of problems (empty ⇒
    OK).  Checks the exact ``collective`` row key set, the coverage (both
    variants measured for every live fraction on ≥1 mesh, and every mesh
    covering the full live-fraction sweep), positive fenced medians, AND
    — on full-geometry documents (the committed artifact) — the headline
    claim: the bitmap-compressed reduce strictly beats the dense psum at
    the LOWEST live fraction on every mesh, and on past-cutoff rows
    (where ``sparse_psum`` runtime-falls-back to dense) costs at most
    ``COLLECTIVE_FALLBACK_SLACK``× dense — the fallback means the
    compressed path never loses more than its tiny bitmap-psum + branch
    overhead.  Smoke documents skip only the claim (reduced reps on
    shared CI runners make a strict wall-clock inequality a coin-flip)."""
    errs: List[str] = []
    for top in ("schema_version", "bench", "jax_backend", "geometry",
                "rows"):
        if top not in doc:
            errs.append(f"missing top-level key {top!r}")
    if errs:
        return errs
    if doc["schema_version"] != SCHEMA_VERSION:
        errs.append(f"schema_version {doc['schema_version']} != "
                    f"{SCHEMA_VERSION}")
    if doc["bench"] != "BENCH_9":
        errs.append(f"bench {doc['bench']!r} != 'BENCH_9'")

    want = set(ROW_KEYS["collective"])
    cells: Dict[Tuple[str, float], Dict[str, float]] = {}
    cutoffs: Dict[str, float] = {}
    for i, row in enumerate(doc["rows"]):
        if row.get("table") != "collective":
            errs.append(f"rows[{i}]: unknown table {row.get('table')!r}")
            continue
        got = set(row)
        if got != want:
            errs.append(f"rows[{i}] (collective): key drift "
                        f"+{sorted(got - want)} -{sorted(want - got)}")
            continue
        if row["variant"] not in COLLECTIVE_VARIANTS:
            errs.append(f"rows[{i}]: unknown variant {row['variant']!r}")
            continue
        if not (isinstance(row["us_median"], (int, float))
                and row["us_median"] > 0):
            errs.append(f"rows[{i}] (collective): non-positive us_median")
            continue
        cells.setdefault((row["mesh"], row["live_frac"]), {})[
            row["variant"]] = row["us_median"]
        cutoffs[row["mesh"]] = row["cutoff"]

    if not cells:
        errs.append("collective coverage: no rows")
        return errs
    by_mesh: Dict[str, set] = {}
    for (mesh_name, live), by_variant in cells.items():
        by_mesh.setdefault(mesh_name, set()).add(live)
        missing = sorted(set(COLLECTIVE_VARIANTS) - set(by_variant))
        if missing:
            errs.append(f"collective coverage: {mesh_name}@{live} missing "
                        f"variants {missing}")
    for mesh_name, lives in by_mesh.items():
        missing = sorted(set(COLLECTIVE_LIVE_FRACS) - lives)
        if missing:
            errs.append(f"collective coverage: {mesh_name} missing live "
                        f"fractions {missing}")

    if doc.get("geometry") != "full":
        return errs                       # claim gated on committed runs
    for mesh_name, lives in sorted(by_mesh.items()):
        cutoff = cutoffs[mesh_name]
        lowest = min(lives)
        for live in sorted(lives):
            by_variant = cells[(mesh_name, live)]
            if set(by_variant) != set(COLLECTIVE_VARIANTS):
                continue                  # coverage error already reported
            bm, dn = by_variant["bitmap"], by_variant["dense_psum"]
            if live == lowest and not bm < dn:
                errs.append(
                    f"claim: bitmap ({bm}us) not faster than dense_psum "
                    f"({dn}us) on {mesh_name}@{live} — the compressed "
                    f"reduce must win where the union is sparse")
            if live > cutoff and not bm <= dn * COLLECTIVE_FALLBACK_SLACK:
                errs.append(
                    f"claim: bitmap ({bm}us) > {COLLECTIVE_FALLBACK_SLACK}x "
                    f"dense_psum ({dn}us) on {mesh_name}@{live} — past the "
                    f"cutoff the runtime fallback must keep the compressed "
                    f"path from losing")
    return errs


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_bench(*, smoke: bool = False) -> dict:
    rows = bench_gemm_rows(smoke=smoke) + bench_train_rows(smoke=smoke)
    selections, log, counters = autotune_session()
    return {
        "schema_version": SCHEMA_VERSION,
        "bench": "BENCH_7",
        "jax_backend": jax.default_backend(),
        "geometry": "smoke" if smoke else "full",
        "rows": rows,
        "autotune": {"counters": counters, "selections": selections,
                     "log": log},
    }


def run_emit_bench(*, smoke: bool = False) -> dict:
    return {
        "schema_version": SCHEMA_VERSION,
        "bench": "BENCH_8",
        "jax_backend": jax.default_backend(),
        "geometry": "smoke" if smoke else "full",
        "rows": bench_emit_rows(smoke=smoke),
    }


def run_collective_bench(*, smoke: bool = False) -> dict:
    return {
        "schema_version": SCHEMA_VERSION,
        "bench": "BENCH_9",
        "jax_backend": jax.default_backend(),
        "geometry": "smoke" if smoke else "full",
        "rows": bench_collective_rows(smoke=smoke),
    }


def write_outputs(doc: dict, out_path: str) -> None:
    from benchmarks.run import RESULTS_DIR, write_rows
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write("\n")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    by_table: Dict[str, List[dict]] = {}
    for row in doc["rows"]:
        by_table.setdefault(row["table"], []).append(row)
    for table, rows in by_table.items():
        write_rows(os.path.join(RESULTS_DIR, f"wallclock_{table}.csv"), rows)
    if doc.get("autotune", {}).get("log"):
        write_rows(os.path.join(RESULTS_DIR, "wallclock_autotune.csv"),
                   doc["autotune"]["log"])


def _checker_for(doc: dict):
    return {"BENCH_8": check_emit_schema,
            "BENCH_9": check_collective_schema}.get(
                doc.get("bench"), check_schema)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced geometry + fewer reps (CI)")
    ap.add_argument("--out", default=BENCH_PATH,
                    help="BENCH JSON path (default: repo-root BENCH_7.json)")
    ap.add_argument("--emit-out", default=BENCH8_PATH,
                    help="BENCH_8 (emit table) JSON path (default: "
                         "repo-root BENCH_8.json)")
    ap.add_argument("--collective-out", nargs="?", const=BENCH9_PATH,
                    default=None, metavar="PATH",
                    help="ALSO generate the BENCH_9 (collective table) "
                         "document at PATH (default when the flag is bare: "
                         "repo-root BENCH_9.json).  Opt-in: without this "
                         "flag BENCH_9 is never written, so BENCH_7/8 "
                         "regenerations cannot clobber the committed "
                         "artifact")
    ap.add_argument("--collective-only", action="store_true",
                    help="generate ONLY the BENCH_9 document (skip "
                         "BENCH_7/8) — the sharded-smoke CI job's mode")
    ap.add_argument("--check", metavar="PATH",
                    help="validate an existing BENCH file and exit "
                         "(the checker is picked by the file's 'bench' key)")
    args = ap.parse_args(argv)

    if args.check:
        with open(args.check) as f:
            doc = json.load(f)
        errs = _checker_for(doc)(doc)
        for e in errs:
            print(f"SCHEMA: {e}", file=sys.stderr)
        print(f"{args.check}: {'DRIFT' if errs else 'ok'}")
        return 1 if errs else 0

    collective_out = args.collective_out
    if args.collective_only and collective_out is None:
        collective_out = BENCH9_PATH

    outputs: List[Tuple[dict, str]] = []
    if not args.collective_only:
        outputs.append((run_bench(smoke=args.smoke), args.out))
        outputs.append((run_emit_bench(smoke=args.smoke), args.emit_out))
    if collective_out is not None:
        outputs.append((run_collective_bench(smoke=args.smoke),
                        collective_out))

    errs = [e for doc, _ in outputs for e in _checker_for(doc)(doc)]
    if errs:
        for e in errs:
            print(f"SCHEMA: {e}", file=sys.stderr)
        return 1
    for doc, path in outputs:
        write_outputs(doc, path)
    for doc, _ in outputs:
        for row in doc["rows"]:
            if row["table"] == "collective":
                print(f"collective,{row['mesh']},live={row['live_frac']},"
                      f"{row['variant']},{row['us_median']:.0f}us "
                      f"±{row['us_iqr']:.0f}")
            else:
                tag = f":{row['variant']}" if row["table"] == "emit" else ""
                print(f"{row['table']},{row['workload']},"
                      f"{row['schedule']}{tag},"
                      f"{row['us_median']:.0f}us ±{row['us_iqr']:.0f}")
        if "autotune" in doc:
            c = doc["autotune"]["counters"]
            print(f"autotune: hits={c['hits']} misses={c['misses']} "
                  f"retunes={c['retunes']} "
                  f"log_rows={len(doc['autotune']['log'])}")
    print("wrote " + " and ".join(path for _, path in outputs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
