"""The spec-driven sparse_gemm collapse: one dispatch API, zero regressions.

Four contract families:
  1. BIT-EXACTNESS NET — ``sparse_gemm`` at G=1 is bit-identical to the
     pre-redesign 2-D orchestration (re-built here on the RETAINED 2-D
     reference kernels in kernels/masked_matmul.py) across
     {predicated, compact} × {none, sigma_prime epilogue} × queue capacity
     {unbounded, exactly-live, overflow→fallback}.
  2. EPILOGUE COMPOSITION — the ``(sigma_prime, bitmap_emit)`` stage tuple
     emits, at accumulator writeback, a bitmap bit-identical to a fresh
     ``bitmap_scan`` of the returned (post-σ′) output, across
     {predicated, compact} × {G=1, grouped} × overflow-fallback; and the
     autotune cache key ignores epilogue/emit_gran (tuples included).
  3. policy→spec resolution (`SparsityPolicy.gemm_spec`) lands the right
     schedule/queue/tiles, incl. grouped_gemm_block degenerate tiles, and
     the default policy still builds queues sort-free
     (``stats.queue_builds("argsort") == 0``).
  4. the dispatcher's normalized ``gemm:<schedule>:<g>`` stats keys and
     ``GemmSpec.launch_geometry``'s pad/grid/queue arithmetic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy as pol
from repro.kernels import ops, ref, stats
from repro.kernels.masked_matmul import (
    compact_masked_matmul_kernel, masked_matmul_kernel,
)
from repro.kernels.ops import GemmMasks, GemmSpec
from repro.kernels.shapes import ceil_to, pad_mask, pad_to


# ---------------------------------------------------------------------------
# 1. bit-exactness vs the pre-redesign 2-D orchestration
# ---------------------------------------------------------------------------

def _legacy_masked_matmul(a, b, out_mask=None, a_mask=None, b_mask=None, *,
                          block, out_dtype=jnp.float32, compact=False,
                          max_active_blocks=None, epilogue_mult=None):
    """The pre-redesign 2-D orchestrator, frozen verbatim on the retained
    2-D kernels — the reference ``sparse_gemm(G=1)`` must match to the bit."""
    m, k = a.shape
    k2, n = b.shape
    bm, bk, bn = block
    mp, kp, np_ = ceil_to(m, bm), ceil_to(k, bk), ceil_to(n, bn)
    ni, nk, nj = mp // bm, kp // bk, np_ // bn
    a_p, b_p = pad_to(a, mp, kp), pad_to(b, kp, np_)
    mult_p = None
    if epilogue_mult is not None:
        mult_p = pad_to(epilogue_mult.astype(jnp.float32), mp, np_)
    om = pad_mask(out_mask, ni, nj)
    am = pad_mask(a_mask, ni, nk)
    bmask = pad_mask(b_mask, nk, nj)

    def _predicated():
        return masked_matmul_kernel(
            a_p, b_p, om, am, bmask, bm=bm, bk=bk, bn=bn,
            out_dtype=out_dtype, epilogue_mult=mult_p, interpret=True)

    if compact:
        s_cap = max_active_blocks if max_active_blocks is not None \
            else ni * nj
        ii, jj, n_live_v = ops.build_queue(om, capacity=s_cap)
        n_live = n_live_v[0]
        n_active = jnp.minimum(n_live, s_cap).reshape(1)

        def _compact():
            compacted = compact_masked_matmul_kernel(
                a_p, b_p, ii, jj, n_active, am, bmask, bm=bm, bk=bk, bn=bn,
                out_dtype=out_dtype, epilogue_mult=mult_p, interpret=True)
            live = (jnp.arange(s_cap) < n_active[0]).astype(out_dtype)
            masked = compacted * live[:, None, None]
            si = jnp.where(jnp.arange(s_cap) < n_active[0], ii, 0)
            sj = jnp.where(jnp.arange(s_cap) < n_active[0], jj, 0)
            out_tiles = jnp.zeros((ni, nj, bm, bn), out_dtype)
            out_tiles = out_tiles.at[si, sj].add(masked)
            return out_tiles.transpose(0, 2, 1, 3).reshape(mp, np_)

        if s_cap >= ni * nj:
            out = _compact()
        else:
            out = jax.lax.cond(n_live > s_cap, _predicated, _compact)
    else:
        out = _predicated()
    return out[:m, :n]


def _operands(m, k, n, key, sparsity=0.6):
    rng = np.random.default_rng(key)
    a = rng.standard_normal((m, k)).astype(np.float32)
    a *= rng.random((m, k)) > sparsity
    b = rng.standard_normal((k, n)).astype(np.float32)
    mask = (rng.random((m, n)) > sparsity).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b), jnp.asarray(mask)


@pytest.mark.parametrize("shape", [(40, 24, 48), (33, 17, 25), (32, 32, 32)])
@pytest.mark.parametrize("schedule", ["predicated", "compact"])
@pytest.mark.parametrize("epilogue", ["none", "sigma_prime"])
def test_g1_sparse_gemm_bit_exact_vs_pre_redesign(shape, schedule, epilogue):
    """ACCEPTANCE: the G=1 lowering of the grouped engine reproduces the
    old 2-D orchestration to the BIT on every schedule × epilogue cell."""
    m, k, n = shape
    a, b, mask = _operands(m, k, n, key=hash(shape) % 1000)
    bm, bk, bn = 8, 8, 16
    om = ref.block_any_nonzero(
        jnp.pad(mask, ((0, -m % bm), (0, -n % bn))), bm, bn)
    am = ref.block_any_nonzero(
        jnp.pad(a, ((0, -m % bm), (0, -k % bk))), bm, bk)
    mult = mask if epilogue == "sigma_prime" else None
    spec = GemmSpec(block=(bm, bk, bn), schedule=schedule, epilogue=epilogue,
                    interpret=True)
    got = ops.sparse_gemm(a, b, GemmMasks(om, am, None), spec,
                          epilogue_mult=mult)
    want = _legacy_masked_matmul(
        a, b, om, am, block=(bm, bk, bn),
        compact=(schedule == "compact"), epilogue_mult=mult)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("epilogue", ["none", "sigma_prime"])
@pytest.mark.parametrize("cap_kind", ["exact", "overflow"])
def test_g1_bounded_queue_and_overflow_bit_exact(epilogue, cap_kind):
    """Compact × bounded capacity: exactly-live stays on the queue path,
    one-below-live triggers the predicated fallback — both bit-identical
    to the pre-redesign orchestration of the same request."""
    m, k, n = 40, 24, 48
    a, b, mask = _operands(m, k, n, key=7)
    om = ref.block_any_nonzero(mask, 8, 16)
    n_live = int(np.asarray(om).sum())
    cap = n_live if cap_kind == "exact" else n_live - 1
    mult = mask if epilogue == "sigma_prime" else None
    spec = GemmSpec(block=(8, 8, 16), schedule="compact", epilogue=epilogue,
                    max_active_blocks=cap, interpret=True)
    got = ops.sparse_gemm(a, b, GemmMasks(out=om), spec, epilogue_mult=mult)
    want = _legacy_masked_matmul(a, b, om, block=(8, 8, 16), compact=True,
                                 max_active_blocks=cap, epilogue_mult=mult)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ...and both equal the oracle (the fallback never truncates)
    oracle = ref.masked_matmul(a, b, out_mask=om, bm=8, bk=8, bn=16,
                               epilogue_mult=mult)
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# 2. composable epilogue stages — bitmap_emit at accumulator writeback
# ---------------------------------------------------------------------------

def _scan_after_gemm_reference(out, emit_gran):
    """The separate-pass producer this PR retires: a fresh ``bitmap_scan``
    of the (already returned) GEMM output.  The emitted bitmap must equal
    it bit-for-bit."""
    return ops.bitmap_scan(out, block=emit_gran, kind="ref")


@pytest.mark.parametrize("schedule", ["predicated", "compact", "dense"])
@pytest.mark.parametrize("stages", [("bitmap_emit",),
                                    ("sigma_prime", "bitmap_emit")])
def test_emit_epilogue_matches_scan_after_gemm_g1(schedule, stages):
    """ACCEPTANCE: the emitted bitmap == scan-of-output, and the output
    itself is unchanged by staging emission — on every schedule, with and
    without the σ′ stage composed in (bits describe POST-σ′ values)."""
    m, k, n = 40, 24, 48
    a, b, mask = _operands(m, k, n, key=23)
    om = ref.block_any_nonzero(
        jnp.pad(mask, ((0, -m % 8), (0, -n % 16))), 8, 16)
    mult = mask if "sigma_prime" in stages else None
    base = GemmSpec(block=(8, 8, 16), schedule=schedule,
                    epilogue="sigma_prime" if mult is not None else "none",
                    interpret=True)
    plain = ops.sparse_gemm(a, b, GemmMasks(out=om), base,
                            epilogue_mult=mult)
    spec = base.with_(epilogue=stages, emit_gran=(4, 4))
    out, bits = ops.sparse_gemm(a, b, GemmMasks(out=om), spec,
                                epilogue_mult=mult)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    want = _scan_after_gemm_reference(out, (4, 4))
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(want))


@pytest.mark.parametrize("cap_kind", ["unbounded", "exact", "overflow"])
def test_emit_epilogue_grouped_and_overflow_fallback(cap_kind):
    """Grouped emission across queue capacities: the runtime predicated
    fallback must return the same (out, bits) pytree as the queue path."""
    g, m, k, n = 3, 24, 16, 24
    a, b, mask = _operands(m, k, n, key=29)
    ag = jnp.stack([a, a * 2, a * 3])
    bg = jnp.stack([b, b, b])
    omg = jnp.stack([ref.block_any_nonzero(mask, 8, 8)] * g)
    multg = jnp.stack([mask, mask, mask])
    n_live = int(np.asarray(omg).sum())
    cap = {"unbounded": None, "exact": n_live,
           "overflow": n_live - 1}[cap_kind]
    spec = GemmSpec(block=(8, 8, 8), groups=g, schedule="compact",
                    epilogue=("sigma_prime", "bitmap_emit"),
                    emit_gran=(8, 8), max_active_blocks=cap, interpret=True)
    out, bits = ops.sparse_gemm(ag, bg, GemmMasks(out=omg), spec,
                                epilogue_mult=multg)
    want_out = ops.sparse_gemm(
        ag, bg, GemmMasks(out=omg),
        spec.with_(epilogue="sigma_prime", emit_gran=None,
                   max_active_blocks=None),
        epilogue_mult=multg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    for gi in range(g):
        want_bits = _scan_after_gemm_reference(out[gi], (8, 8))
        np.testing.assert_array_equal(np.asarray(bits[gi]),
                                      np.asarray(want_bits))


def test_autotune_key_excludes_epilogue_tuple_and_emit_gran():
    """The autotuner must share measurements across epilogue variants: the
    cache key is (block, groups, queue_builder, padded) — staging
    sigma_prime/bitmap_emit (and the emit_gran it requires) or changing
    out_dtype must NOT fork the key."""
    from repro.kernels import autotune

    dims = (64, 32, 64)
    base = GemmSpec(block=(8, 8, 8), schedule="compact")
    variants = [
        base,
        base.with_(epilogue=("sigma_prime",)),
        base.with_(epilogue=("bitmap_emit",), emit_gran=(4, 8)),
        base.with_(epilogue=("sigma_prime", "bitmap_emit"),
                   emit_gran=(8, 8)),
        base.with_(schedule="predicated", out_dtype=jnp.bfloat16),
    ]
    keys = {autotune.key_for(s, dims) for s in variants}
    assert len(keys) == 1, keys


# ---------------------------------------------------------------------------
# 3. policy → spec resolution
# ---------------------------------------------------------------------------

def test_policy_gemm_spec_resolution():
    p = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 16, 8),
                            queue_builder="argsort")
    s = p.gemm_spec(groups=1)
    assert (s.schedule, s.block, s.queue_builder, s.groups) \
        == ("compact", (8, 16, 8), "argsort", 1)
    assert p.with_(work_redistribution=False).gemm_spec().schedule \
        == "predicated"
    assert pol.IN_OUT.gemm_spec().schedule == "dense"       # xla_ref
    assert pol.DC.gemm_spec().schedule == "dense"
    # degenerate grouped tiles == the grouped_gemm_block rule, any G incl. 1
    for g in (1, 8):
        s = p.gemm_spec(groups=g, dims=(4096, 9, 1), grans=(1, 1, 1))
        assert s.block == pol.grouped_gemm_block(p, (4096, 9, 1), (1, 1, 1))
        assert s.block == (8, 9, 1)
    # fused-epilogue declaration (normalized to the canonical stage tuple)
    assert p.gemm_spec(fused_epilogue=True).epilogue == ("sigma_prime",)
    assert p.gemm_spec(fused_epilogue=False).epilogue == ()


def test_default_policy_training_step_is_sort_free_and_spec_routed():
    """End-to-end: an IN_OUT_WR step dispatches every GEMM through
    sparse_gemm (compact schedule) and never builds a queue by sorting."""
    from repro.core.sparse_linear import relu_matmul

    policy = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    stats.reset()
    jax.grad(lambda x, w: (relu_matmul(x, w, policy) ** 2).sum(), (0, 1))(x, w)
    assert stats.queue_builds("argsort") == 0, stats.counts()
    assert stats.gemm_launches() == stats.gemm_launches(schedule="compact"), \
        stats.counts()
    assert stats.gemm_launches(schedule="compact", groups=1) == 3  # y, dx, dW


# ---------------------------------------------------------------------------
# 4. spec validation, stats keys, launch geometry
# ---------------------------------------------------------------------------

def test_gemm_spec_validates():
    with pytest.raises(ValueError, match="schedule"):
        GemmSpec(schedule="eager")
    with pytest.raises(ValueError, match="epilogue"):
        GemmSpec(epilogue="relu")
    with pytest.raises(ValueError, match="epilogue"):
        GemmSpec(epilogue=("sigma_prime", "sigma_prime"))   # duplicate stage
    with pytest.raises(ValueError, match="emit_gran"):
        GemmSpec(epilogue=("bitmap_emit",))                 # gran required
    with pytest.raises(ValueError, match="emit_gran"):
        GemmSpec(epilogue=("bitmap_emit",), emit_gran=(3, 8))  # 3 ∤ bm=128
    with pytest.raises(ValueError, match="emit_gran"):
        GemmSpec(emit_gran=(8, 8))                          # gran w/o stage
    # legacy spellings still normalize
    assert GemmSpec(epilogue="none").epilogue == ()
    assert GemmSpec(epilogue=None).epilogue == ()
    assert GemmSpec(epilogue="sigma_prime").epilogue == ("sigma_prime",)
    # canonical order is enforced regardless of declaration order
    s = GemmSpec(epilogue=("bitmap_emit", "sigma_prime"), emit_gran=(8, 8))
    assert s.epilogue == ("sigma_prime", "bitmap_emit")
    assert s.fuses_mult and s.emits_bitmap
    with pytest.raises(ValueError, match="groups"):
        GemmSpec(groups=0)
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="groups"):
        ops.sparse_gemm(a, a, None, GemmSpec(groups=2))
    with pytest.raises(ValueError, match="epilogue"):
        ops.sparse_gemm(a, a, None, GemmSpec(), epilogue_mult=a)
    with pytest.raises(ValueError, match="epilogue"):
        ops.sparse_gemm(a, a, None, GemmSpec(epilogue="sigma_prime"))
    with pytest.raises(ValueError, match="group axis"):
        ops.sparse_gemm(a[None], a[None], None, GemmSpec(groups=2))


def test_dispatch_records_normalized_stats_keys():
    a = jnp.ones((8, 8), jnp.float32)
    stats.reset()
    ops.sparse_gemm(a, a, None, GemmSpec(block=(8, 8, 8)))
    ops.sparse_gemm(a[None], a[None], None,
                    GemmSpec(block=(8, 8, 8), schedule="compact", groups=1))
    ops.sparse_gemm(a, a, None, GemmSpec(schedule="dense"))
    c = stats.counts()
    assert c["gemm:predicated:1"] == 1 and c["gemm:compact:1"] == 1 \
        and c["gemm:dense:1"] == 1, c
    assert stats.gemm_launches() == 3
    assert stats.gemm_launches(schedule="compact") == 1


def test_launch_geometry_matches_dispatch_contract():
    s = GemmSpec(block=(8, 8, 16), groups=3, schedule="compact")
    g = s.launch_geometry(33, 17, 25)           # ni=5, nk=3, nj=2
    assert g["padded"] == (3, 40, 24, 32)
    assert g["queue_capacity"] == 3 * 5 * 2
    assert g["grid"] == (30, 3)
    assert g["fallback_grid"] == (3, 5, 2, 3)
    s2 = s.with_(schedule="predicated", groups=1)
    assert s2.launch_geometry(33, 17, 25)["grid"] == (1, 5, 2, 3)
    assert s.with_(schedule="dense").launch_geometry(33, 17, 25)["grid"] == ()


# ---------------------------------------------------------------------------
# 5. launch-geometry edge cases: the degenerate shapes real models hit
# ---------------------------------------------------------------------------

def test_launch_geometry_degenerate_depthwise_k():
    """Depthwise conv: per-group K = R·S = 9, far below the nominal 128
    block.  grouped_gemm_block must shrink the K edge to 9 (one K step,
    per-patch-row masking still live), not pad 14x and mask nothing."""
    p = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(128, 128, 128))
    m, k, n = 64, 9, 8                      # (M, R*S, C_out/G) per group
    spec = p.gemm_spec(groups=8, dims=(m, k, n))
    assert spec.block[1] == 9               # degenerate K edge
    g = spec.launch_geometry(m, k, n)
    assert g["padded"][2] == 9              # K axis NOT padded to 128
    assert g["grid"][1] == 1                # nk == 1: a single K step
    # masking stays live: the queue spans all groups' output tiles
    assert g["queue_capacity"] == 8 * g["fallback_grid"][1] \
        * g["fallback_grid"][2]


def test_launch_geometry_g1_keeps_leading_group_axis():
    """G=1 is the 2-D special case but the launch stays a GROUPED launch:
    the grid keeps its leading group axis of extent 1 (one kernel family,
    docs/gemm_api.md), and padding only touches the trailing dims."""
    spec = GemmSpec(block=(8, 8, 8), groups=1, schedule="predicated")
    g = spec.launch_geometry(12, 20, 8)
    assert g["grid"] == (1, 2, 1, 3)        # leading axis present, extent 1
    assert g["padded"] == (1, 16, 24, 8)
    # compact at G=1: queue capacity counts (1, ni, nj) tiles
    gc = spec.with_(schedule="compact").launch_geometry(12, 20, 8)
    assert gc["queue_capacity"] == 1 * 2 * 1
    assert gc["grid"] == (2, 3)
    assert gc["fallback_grid"] == (1, 2, 1, 3)


def test_exact_capacity_queue_leaves_dump_slot_unused():
    """n_live == capacity: every queue slot is live, nothing overflows into
    the dump slot past the queue, and the queue is the reference order."""
    from repro.core.workredist import static_queue_order

    bmp = np.ones((4, 4), np.int32)         # 16 live == capacity 16
    ii, jj, n_live = ops.build_queue(jnp.asarray(bmp), capacity=16)
    ref_ii, ref_jj, ref_n = static_queue_order(bmp, 16)
    assert int(n_live[0]) == ref_n == 16
    assert np.array_equal(ii, ref_ii) and np.array_equal(jj, ref_jj)

    # the dispatcher's geometry agrees: exactly-live max_active_blocks
    # yields a queue of that capacity with the grid sized to it
    spec = GemmSpec(block=(8, 8, 8), groups=1, schedule="compact",
                    max_active_blocks=16)
    g = spec.launch_geometry(32, 16, 32)
    assert g["queue_capacity"] == 16
    assert g["grid"] == (16, 2)


@pytest.mark.parametrize("schedule", ["predicated", "compact"])
@pytest.mark.parametrize("precision,full", [
    (None, False), ("default", False), ("bfloat16", False),
    ("high", True), ("tensorfloat32", True), ("highest", True),
    ("float32", True)])
def test_kernel_dot_follows_default_matmul_precision(schedule, precision,
                                                     full):
    """The kernels' MXU product takes HIGHEST wherever
    ``jax.default_matmul_precision`` asks for more than one bf16 pass, so a
    kernel never computes below the requested precision — the chip check
    computes both sides of its comparison at "highest"."""
    import contextlib

    a = jnp.ones((16, 16), jnp.float32)
    m = jnp.ones((2, 2), jnp.int32)
    spec = GemmSpec(block=(8, 8, 8), schedule=schedule, interpret=True)
    ctx = jax.default_matmul_precision(precision) if precision \
        else contextlib.nullcontext()
    with ctx:
        jaxpr = jax.make_jaxpr(lambda x: ops.sparse_gemm(
            x, x, GemmMasks(out=m, a=m, b=m), spec))(a)
    kernels = [str(e.params["jaxpr"]) for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    assert ("HIGHEST" in kernels[0]) == full
