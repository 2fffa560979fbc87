"""Sparsity-on-the-wire: bitmap-compressed collectives and the shard_map
training step (docs/sharding.md).

Two device regimes share this file:

  * Any-device cells run on whatever the process sees (tier-1 CI: ONE
    device — conftest.py deliberately sets no
    ``--xla_force_host_platform_device_count`` override).  A 1-device
    psum is still the full traced path: queue build, compact gather,
    runtime cutoff branch, counters.
  * ``requires8`` cells assert the actual multi-shard contracts
    (spmd-vs-jit equivalence, one-encode-across-the-mesh) and skip
    unless ≥8 devices are visible.  The sanctioned way to provide them
    is the ENVIRONMENT, not conftest: the ``sharded-smoke`` CI job (and
    a local run) exports ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    before pytest starts.  ``test_eight_device_rerun_subprocess`` (slow)
    does exactly that from a 1-device parent, so the 8-device cells stay
    reachable from a plain checkout too.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import policy as pol
from repro.core.sparse_linear import _act_matmul_fwd, act_matmul
from repro.core.sparse_tensor import lookup_grad_bitmap
from repro.kernels import stats
from repro.sharding import spmd_step
from repro.sharding.collectives import dense_psum, psum_grads, sparse_psum
from repro.sharding.partition import bitmap_pspec

PALLAS = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 16, 8))

requires8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(set in the environment, never in conftest)")


def _data_mesh():
    return jax.make_mesh((jax.device_count(),), ("data",))


def _correlated_stack(n_dev, m, n, gran, live, seed=0):
    """(n_dev, m, n) data + (n_dev, mb, nb) bitmaps with the SAME block
    pattern on every shard — the dW regime (shards share σ′ geometry).
    The live count is exact: an uncorrelated Bernoulli draw per shard
    would union to ~dense and defeat the compressed path."""
    g0, g1 = gran
    mb, nb = -(-m // g0), -(-n // g1)
    rng = np.random.default_rng(seed)
    count = max(1, min(mb * nb, round(live * mb * nb)))
    bm = np.zeros(mb * nb, np.int32)
    bm[rng.permutation(mb * nb)[:count]] = 1
    bm = bm.reshape(mb, nb)
    expand = np.repeat(np.repeat(bm, g0, 0), g1, 1)[:m, :n]
    data = rng.standard_normal((n_dev, m, n)).astype(np.float32) \
        * expand[None].astype(np.float32)
    bits = np.broadcast_to(bm, (n_dev, mb, nb)).copy()
    return data, bits


def _reduce_fn(gran, cutoff, mesh=None):
    mesh = mesh or _data_mesh()
    axes = tuple(mesh.axis_names)

    def body(x, b):
        return sparse_psum(x[0], b[0], gran, axis_name=axes, cutoff=cutoff,
                           return_bits=True)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(axes), P(axes)),
        out_specs=(P(), P()), check_vma=False))


# ---------------------------------------------------------------------------
# sparse_psum == dense all-reduce (any device count)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("live", [0.25, 1.0])
def test_sparse_psum_matches_dense_allreduce(live):
    """The compressed reduce (and its past-cutoff fallback) is EXACT
    against the numpy sum of all shard contributions: a union-dead block
    is all-zero on every shard, so dropping it from the wire loses
    nothing; live blocks travel unmodified."""
    n_dev = jax.device_count()
    gran = (4, 4)
    data, bits = _correlated_stack(n_dev, 32, 32, gran, live, seed=3)
    stats.reset()
    out, union = _reduce_fn(gran, cutoff=0.5)(
        jnp.asarray(data), jnp.asarray(bits))
    jax.block_until_ready(out)
    np.testing.assert_allclose(np.asarray(out), data.sum(0), atol=1e-5)
    c = stats.counts()
    assert c.get("collective:bitmap_psum", 0) >= 1, c
    if live <= 0.5:
        # compressed path taken on every shard, fallback on none
        assert c.get("collective:compressed", 0) == n_dev, c
        assert c.get("collective:dense_fallback", 0) == 0, c
        np.testing.assert_array_equal(
            (np.asarray(union) > 0).astype(np.int32), bits[0])
    else:
        assert c.get("collective:dense_fallback", 0) == n_dev, c
        assert c.get("collective:compressed", 0) == 0, c


def test_sparse_psum_cutoff_admitting_all_blocks_is_dense():
    """capacity ≥ nblocks ⇒ the compressed machinery cannot move fewer
    bytes than the dense reduce, so sparse_psum short-circuits to the
    tagged dense path at trace time (no queue, no cond)."""
    n_dev = jax.device_count()
    gran = (4, 4)
    data, bits = _correlated_stack(n_dev, 8, 8, gran, 0.5, seed=4)
    stats.reset()
    out, _ = _reduce_fn(gran, cutoff=1.0)(
        jnp.asarray(data), jnp.asarray(bits))
    jax.block_until_ready(out)
    np.testing.assert_allclose(np.asarray(out), data.sum(0), atol=1e-5)
    c = stats.counts()
    assert c.get("collective:dense", 0) >= 1, c
    assert c.get("collective:compressed", 0) == 0, c


def test_dense_psum_records_collective_key():
    mesh = _data_mesh()
    x = jnp.ones((jax.device_count(), 4, 4), jnp.float32)
    stats.reset()
    fn = jax.jit(jax.shard_map(lambda v: dense_psum(v[0], axis_name="data"),
                           mesh=mesh, in_specs=(P("data"),),
                           out_specs=P(), check_vma=False))
    jax.block_until_ready(fn(x))
    assert stats.counts().get("collective:dense", 0) == 1


def test_psum_grads_routes_by_registry():
    """Pytree leaves with a registered bitmap take the compressed reduce;
    bias-like leaves (no bitmap) the tagged dense one — and the registry
    consult is a PEEK (no registry:miss inflation from structural
    misses).  The grads are produced INSIDE the shard_map body trace, as
    the training step does: the registry is keyed by object identity, so
    the WG bitmap registered by the backward pass is only visible on the
    very tracers that backward returned."""
    mesh = _data_mesh()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    params = {"w": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}
    stats.reset()

    def body(p):
        def loss(q):
            return ((act_matmul(x, q["w"], PALLAS, "relu")
                     + q["b"]) ** 2).sum()
        grads = jax.grad(loss)(p)
        return psum_grads(grads, axis_name=("data",), cutoff=0.5)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False))
    out = fn(params)
    jax.block_until_ready(out)
    c = stats.counts()
    assert c.get("collective:bitmap_psum", 0) == 1, c   # dw leaf compressed
    assert c.get("collective:dense", 0) == 1, c         # bias leaf dense

    # peek, not lookup: the backward pass itself records its own registry
    # consults, but routing the grads through psum_grads must add ZERO
    # misses on top (every non-bitmap leaf it probes is a structural miss
    # that would otherwise poison the guard's miss-delta budget)
    misses_with = c.get("registry:miss", 0)
    stats.reset()
    jax.jit(lambda p: jax.grad(
        lambda q: ((act_matmul(x, q["w"], PALLAS, "relu")
                    + q["b"]) ** 2).sum())(p)).lower(params)
    assert stats.counts().get("registry:miss", 0) == misses_with


# ---------------------------------------------------------------------------
# WG bitmap registration (the registry hand-off the collective consumes)
# ---------------------------------------------------------------------------

def test_wg_bitmap_registered_for_linear_grads():
    """The backward dW of act_matmul registers a derived WG bitmap against
    the exact returned array, and the bitmap is CONSERVATIVE: a dead bit
    ⇒ that block of dW is exactly zero (masks may only err toward live —
    the invariant that makes dropping dead blocks from the wire exact)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((32, 16)) *
                    (rng.random((32, 16)) > 0.6), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    dw = jax.grad(lambda w_: (act_matmul(x, w_, PALLAS, "relu") ** 2).sum(),
                  )(w)
    hit = lookup_grad_bitmap(dw, peek=True)
    assert hit is not None
    bitmap, gran = hit
    g0, g1 = gran
    bnp, dnp = np.asarray(bitmap), np.asarray(dw)
    for i in range(bnp.shape[0]):
        for j in range(bnp.shape[1]):
            if bnp[i, j] == 0:
                blockv = dnp[i * g0:(i + 1) * g0, j * g1:(j + 1) * g1]
                assert not blockv.any(), (i, j)


# ---------------------------------------------------------------------------
# Mask slicing (pure contract — no mesh needed)
# ---------------------------------------------------------------------------

def test_shard_bitmap_is_row_slice_of_global_bitmap():
    """The spmd design's no-rescan guarantee rests on this: encoding a
    row-shard of the batch yields EXACTLY the matching row-slice of the
    global forward bitmap, whenever the shard boundary lands on a
    granularity-cell boundary (which `partition.bitmap_pspec` enforces
    for sharded carriers).  So per-shard SparseTensor masks ARE slices of
    the one forward bitmap — nothing is recomputed per shard."""
    n_shards, m, k = 8, 64, 16
    rng = np.random.default_rng(6)
    x_pre = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, 8)), jnp.float32)
    _, (st_g, _) = _act_matmul_fwd(x_pre, w, PALLAS, "relu")
    g0 = st_g.gran[0]
    rows = m // n_shards
    assert rows % g0 == 0, "shard boundary must land on a bitmap cell"
    for s in range(n_shards):
        shard = x_pre[s * rows:(s + 1) * rows]
        _, (st_s, _) = _act_matmul_fwd(shard, w, PALLAS, "relu")
        np.testing.assert_array_equal(
            np.asarray(st_s.bitmap),
            np.asarray(st_g.bitmap)[s * rows // g0:(s + 1) * rows // g0])


def test_bitmap_pspec_alignment_rules():
    """A bitmap dim mirrors its data dim's mesh axes only when every
    shard holds a whole number of granularity cells
    (dim % (axis_size · gran) == 0); otherwise it replicates."""
    mesh = _data_mesh()
    n = jax.device_count()
    gran = (8, 8)
    # aligned: rows divisible by axis_size * gran[0]
    spec = bitmap_pspec((8 * 8 * n, 32), P("data", None), gran, mesh)
    assert spec == P("data", None)
    # unsharded dims always replicate on the bitmap
    spec = bitmap_pspec((8 * 8 * n, 32), P(None, None), gran, mesh)
    assert spec == P(None, None)
    if n > 1:
        # rows divisible by gran but NOT by axis_size*gran: a shard
        # boundary would straddle a cell → replicate (conservative)
        spec = bitmap_pspec((8 * (n + 1), 32), P("data", None), gran, mesh)
        assert spec == P(None, None)


# ---------------------------------------------------------------------------
# Fault containment (the chaos-matrix case, run inline)
# ---------------------------------------------------------------------------

def test_collective_drop_fault_detected_and_survived():
    from repro.runtime import faults
    row = faults._case_collective_drop()
    assert row.detected, row.detail
    assert row.survived, row.detail
    assert row.ok


# ---------------------------------------------------------------------------
# BENCH_9 schema
# ---------------------------------------------------------------------------

def test_bench9_smoke_document_passes_schema():
    from benchmarks import wallclock
    doc = wallclock.run_collective_bench(smoke=True)
    assert wallclock.check_collective_schema(doc) == []
    assert doc["bench"] == "BENCH_9"
    # runtime counting restored after the bench disabled it
    assert stats.set_runtime_counting(True) is True


def test_bench9_schema_rejects_drift():
    from benchmarks import wallclock
    rows = []
    for mesh_name in ("8",):
        for live in wallclock.COLLECTIVE_LIVE_FRACS:
            for variant in wallclock.COLLECTIVE_VARIANTS:
                rows.append({
                    "table": "collective", "mesh": mesh_name, "devices": 8,
                    "m": 512, "n": 256, "block": "32x256",
                    "live_frac": live,
                    "cutoff": wallclock.COLLECTIVE_CUTOFF,
                    "variant": variant, "us_median": 100.0, "us_iqr": 1.0,
                    "reps": 3, "warmup": 1})
    doc = {"schema_version": wallclock.SCHEMA_VERSION, "bench": "BENCH_9",
           "jax_backend": "cpu", "geometry": "smoke", "rows": rows}
    assert wallclock.check_collective_schema(doc) == []

    bad = {**doc, "rows": [dict(r, extra=1) for r in rows]}
    assert any("key drift" in e
               for e in wallclock.check_collective_schema(bad))
    bad = {**doc, "rows": [dict(r, variant="gossip") for r in rows]}
    assert any("variant" in e
               for e in wallclock.check_collective_schema(bad))
    bad = {**doc, "rows": rows[:2]}
    assert any("coverage" in e
               for e in wallclock.check_collective_schema(bad))


def test_bench9_full_geometry_claim_is_enforced():
    from benchmarks import wallclock
    us = {"dense_psum": 100.0, "bitmap": 150.0}   # bitmap loses everywhere

    def mk(geometry):
        rows = []
        for live in wallclock.COLLECTIVE_LIVE_FRACS:
            for variant in wallclock.COLLECTIVE_VARIANTS:
                rows.append({
                    "table": "collective", "mesh": "8", "devices": 8,
                    "m": 8192, "n": 2048, "block": "128x2048",
                    "live_frac": live,
                    "cutoff": wallclock.COLLECTIVE_CUTOFF,
                    "variant": variant, "us_median": us[variant],
                    "us_iqr": 1.0, "reps": 7, "warmup": 2})
        return {"schema_version": wallclock.SCHEMA_VERSION,
                "bench": "BENCH_9", "jax_backend": "cpu",
                "geometry": geometry, "rows": rows}

    # smoke documents are exempt from the claim …
    assert wallclock.check_collective_schema(mk("smoke")) == []
    # … full documents are not: losing at the lowest live fraction and
    # past the cutoff both fail
    errs = wallclock.check_collective_schema(mk("full"))
    assert any("not faster" in e for e in errs)
    assert any("fallback" in e for e in errs)


# ---------------------------------------------------------------------------
# 8-device contracts (the actual mesh)
# ---------------------------------------------------------------------------

def _ffn_loss_and_batch(tokens=64):
    from repro.models.ffn import FFNConfig, ffn_apply, ffn_init
    cfg = FFNConfig(d_model=16, d_ff=32, activation="relu",
                    sparse_policy=PALLAS)
    params = ffn_init(jax.random.key(20), cfg)
    x = jax.random.normal(jax.random.key(21), (tokens, 16), jnp.float32)
    y = jax.random.normal(jax.random.key(22), (tokens, 16), jnp.float32)

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((ffn_apply(p, xb, cfg) - yb) ** 2)

    return loss_fn, params, (x, y)


@requires8
def test_ffn_spmd_grads_match_single_device_jit():
    """The shard_map step is numerically the single-device jit of the
    same loss over the full batch (psum accumulation-order tolerance) —
    WITH the gradient all-reduce routed through the bitmap-compressed
    collective (the WG-bitmap registry hand-off survives the
    value_and_grad trace inside the shard_map body)."""
    loss_fn, params, batch = _ffn_loss_and_batch()
    mesh = jax.make_mesh((8,), ("data",))
    stats.reset()
    f = spmd_step.make_spmd_grad_fn(loss_fn, mesh)
    loss_s, grads_s = f(params, batch)
    jax.block_until_ready(loss_s)
    c = stats.counts()

    loss_j, grads_j = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_j),
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_s), jax.tree.leaves(grads_j)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    # lifecycle contracts, mesh-wide: the body traces ONCE, so exactly
    # one fused encode per activation — and never a rescan anywhere
    assert sum(v for k, v in c.items()
               if k.startswith(("scan:", "scan_pallas:"))) == 0, c
    assert c.get("encode:act", 0) == 1, c
    # the FFN params are exactly two 2-D weight mats (no biases) and BOTH
    # registry peeks hit: every gradient leaf takes the compressed reduce
    assert c.get("collective:bitmap_psum", 0) == 2, c
    assert c.get("collective:dense", 0) == 0, c


@requires8
def test_cnn_spmd_grads_match_single_device_jit():
    """Same contract for the CNN (vgg16 smoke geometry, batch 8 → one
    image per shard): conv dW grads carry no registered bitmaps (only
    linear layers do), so their reduces are tagged dense — still zero
    rescans and one encode per activation across the mesh."""
    from repro.models.cnn import build_cnn
    model = build_cnn("vgg16", image_size=8, width=0.0625, num_classes=10)
    params = model.init(jax.random.key(30))
    img = jax.random.normal(jax.random.key(31), (8, 8, 8, 3), jnp.float32)
    lbl = jax.random.randint(jax.random.key(32), (8,), 0, 10)

    def loss_fn(p, batch):
        return model.loss(p, batch["img"], batch["lbl"], PALLAS)

    mesh = jax.make_mesh((8,), ("data",))
    stats.reset()
    f = spmd_step.make_spmd_grad_fn(loss_fn, mesh)
    loss_s, grads_s = f(params, {"img": img, "lbl": lbl})
    jax.block_until_ready(loss_s)
    c = stats.counts()
    assert sum(v for k, v in c.items()
               if k.startswith(("scan:", "scan_pallas:"))) == 0, c
    n_encodes = c.get("encode:act", 0)
    assert n_encodes >= 1, c

    loss_j, grads_j = jax.jit(jax.value_and_grad(loss_fn))(
        params, {"img": img, "lbl": lbl})
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_j),
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_s), jax.tree.leaves(grads_j)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)

    # the single-device trace must not have needed MORE encodes than the
    # whole mesh did: one per activation, period
    stats.reset()
    jax.block_until_ready(
        jax.jit(jax.value_and_grad(loss_fn))(params,
                                             {"img": img, "lbl": lbl}))
    assert stats.counts().get("encode:act", 0) == n_encodes


@requires8
def test_spmd_equivalent_across_mesh_shapes():
    """(8,) and (2, 4) meshes produce identical global grads — the
    collective is axis-set agnostic (psum over ('data',) ≡ over
    ('data', 'pod') when they cover the same devices)."""
    loss_fn, params, batch = _ffn_loss_and_batch()
    f1 = spmd_step.make_spmd_grad_fn(
        loss_fn, jax.make_mesh((8,), ("data",)))
    f2 = spmd_step.make_spmd_grad_fn(
        loss_fn, jax.make_mesh((2, 4), ("pod", "data")))
    l1, g1 = f1(params, batch)
    l2, g2 = f2(params, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@requires8
def test_sparse_psum_compressed_beats_union_of_uncorrelated_masks():
    """Uncorrelated per-shard masks union to ~dense: the runtime cutoff
    must detect that and take the dense fallback — per-shard sparsity
    that doesn't survive the union is not allowed to pretend."""
    n_dev, gran = 8, (4, 4)
    rng = np.random.default_rng(9)
    data = np.zeros((n_dev, 32, 32), np.float32)
    bits = np.zeros((n_dev, 8, 8), np.int32)
    for s in range(n_dev):
        bm = (rng.random((8, 8)) < 0.3).astype(np.int32)
        bm[0, 0] = 1
        bits[s] = bm
        data[s] = rng.standard_normal((32, 32)).astype(np.float32) \
            * np.repeat(np.repeat(bm, 4, 0), 4, 1)
    stats.reset()
    out, union = _reduce_fn(gran, cutoff=0.5)(
        jnp.asarray(data), jnp.asarray(bits))
    jax.block_until_ready(out)
    np.testing.assert_allclose(np.asarray(out), data.sum(0), atol=1e-5)
    c = stats.counts()
    # the union at 8 × 30% uncorrelated ≈ 94% live ⇒ every shard fell back
    assert c.get("collective:dense_fallback", 0) == n_dev, c


# ---------------------------------------------------------------------------
# 8-device bootstrap from a 1-device checkout
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_eight_device_rerun_subprocess():
    """Re-run this file's fast cells under an 8-virtual-device child
    process — the conftest-sanctioned way to get a mesh on a laptop.
    Skipped where the environment already provides ≥8 devices (CI's
    sharded-smoke job runs the file directly)."""
    if jax.device_count() >= 8:
        pytest.skip("already ≥8 devices; the cells above ran directly")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow", __file__],
        env=env, capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout + proc.stderr
