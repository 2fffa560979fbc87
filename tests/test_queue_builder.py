"""The compaction contract: the prefix-sum queue builder emits
EXACTLY the WDU reference order (``core.workredist.static_queue_order`` —
row-major "lexicographically smallest state tuple first"), bit-for-bit,
for any bitmap — and the compact matmul path never sorts on the default
policy and never truncates on overflow.

Deterministic sweeps run everywhere (tier-1); the hypothesis suite (random
bitmaps incl. all-zero / all-one / single-row / ragged shapes) needs the
``dev`` extra and skips cleanly without it, mirroring
tests/test_sparsity_properties.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy as pol
from repro.core.sparse_linear import relu_matmul
from repro.core.workredist import static_queue_order, wdu_dispatch_order
from repro.kernels import ops, ref, stats
from repro.kernels.ops import GemmMasks, GemmSpec

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev extra not installed
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed (dev extra)")

if HAS_HYPOTHESIS:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")


def _assert_queue_equals_reference(bm_np: np.ndarray, capacity: int,
                                   builder: str):
    ii, jj, nl = ops.build_queue(
        jnp.asarray(bm_np, jnp.int32), capacity=capacity, builder=builder)
    ri, rj, rn = static_queue_order(bm_np, capacity=capacity)
    assert int(np.asarray(nl)[0]) == rn
    np.testing.assert_array_equal(np.asarray(ii), ri)
    np.testing.assert_array_equal(np.asarray(jj), rj)


# ---------------------------------------------------------------------------
# deterministic contract sweeps (run without hypothesis)
# ---------------------------------------------------------------------------

EDGE_BITMAPS = [
    np.zeros((4, 4), np.int32),                      # all-zero
    np.ones((4, 4), np.int32),                       # all-one
    np.ones((1, 13), np.int32),                      # single row
    np.ones((11, 1), np.int32),                      # single column
    np.eye(6, dtype=np.int32),                       # diagonal
    (np.indices((5, 9)).sum(0) % 2).astype(np.int32),  # checkerboard
    np.asarray([[0, 1, 1], [1, 0, 0], [0, 0, 1],
                [1, 1, 1], [0, 0, 0]], np.int32),    # ragged rows
]


@pytest.mark.parametrize("builder", ["prefix_sum", "argsort"])
@pytest.mark.parametrize("bm", EDGE_BITMAPS, ids=lambda b: f"{b.shape}")
def test_builders_match_wdu_reference(bm, builder):
    _assert_queue_equals_reference(bm, capacity=bm.size, builder=builder)
    # under-capacity: the first `cap` live slots are preserved, and the
    # returned live count is the TRUE count (the overflow signal)
    _assert_queue_equals_reference(bm, capacity=max(1, bm.size // 3),
                                   builder=builder)


def test_reference_order_is_the_wdu_dispatch_rule():
    bm = (np.indices((7, 6)).sum(0) % 3 == 0).astype(np.int32)
    ii, jj, n = static_queue_order(bm)
    assert list(zip(ii[:n], jj[:n])) == wdu_dispatch_order(bm)


def test_compact_default_policy_builds_queue_with_zero_argsorts():
    """ACCEPTANCE: the compact schedule constructs its queue with zero
    argsort calls on the default (prefix_sum) spec — asserted via the
    kernels.stats counter."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    om = jnp.asarray(rng.random((4, 4)) > 0.5, jnp.int32)
    stats.reset()
    out = ops.sparse_gemm(a, b, GemmMasks(out=om),
                          GemmSpec(block=(8, 8, 8), schedule="compact"))
    assert stats.queue_builds("argsort") == 0, stats.counts()
    assert stats.queue_builds("prefix_sum") == 1, stats.counts()
    assert stats.gemm_launches(schedule="compact", groups=1) == 1
    want = ref.masked_matmul(a, b, out_mask=om, bm=8, bk=8, bn=8)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_end_to_end_training_step_never_sorts_on_default_policy():
    """The whole fwd+bwd of the fused unit under IN_OUT_WR: queues are
    built (compact schedule), none of them by sorting."""
    policy = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    stats.reset()
    jax.grad(lambda x, w: (relu_matmul(x, w, policy) ** 2).sum(), (0, 1))(x, w)
    assert stats.queue_builds() > 0, stats.counts()
    assert stats.queue_builds("argsort") == 0, stats.counts()


@pytest.mark.parametrize("builder", ["prefix_sum", "argsort"])
def test_compact_matmul_same_result_for_both_builders(builder):
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((40, 24)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((24, 48)), jnp.float32)
    mask = (rng.random((40, 48)) > 0.6).astype(np.float32)
    om = ref.block_any_nonzero(jnp.asarray(mask), 8, 16)
    spec = GemmSpec(block=(8, 8, 16))
    got = ops.sparse_gemm(
        a, b, GemmMasks(out=om),
        spec.with_(schedule="compact", queue_builder=builder))
    want = ops.sparse_gemm(a, b, GemmMasks(out=om), spec)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("builder", ["prefix_sum", "argsort"])
def test_overflow_falls_back_bit_exactly_to_predicated(builder):
    """REGRESSION: n_live > max_active_blocks must route to the predicated
    schedule — the result is bit-identical to calling it directly."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    om = jnp.ones((4, 4), jnp.int32)                  # 16 live tiles
    spec = GemmSpec(block=(8, 8, 8), schedule="compact",
                    max_active_blocks=3, queue_builder=builder)
    got = ops.sparse_gemm(a, b, GemmMasks(out=om), spec)
    predicated = ops.sparse_gemm(a, b, GemmMasks(out=om),
                                 GemmSpec(block=(8, 8, 8)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(predicated))
    # ...and under jit, where the live count is a traced value
    f = jax.jit(lambda a, b: ops.sparse_gemm(
        a, b, GemmMasks(out=om), spec.with_(interpret=True)))
    np.testing.assert_array_equal(np.asarray(f(a, b)), np.asarray(predicated))


def test_build_queue_rejects_unknown_builder():
    with pytest.raises(ValueError, match="unknown queue builder"):
        ops.build_queue(jnp.ones((2, 2), jnp.int32), capacity=4,
                        builder="bogosort")


def test_build_queue_jits_and_batches_under_vmap_shapes():
    """The builder must be jit-safe (it sits inside jitted train steps)."""
    bm = jnp.asarray(np.eye(5, dtype=np.int32))
    f = jax.jit(lambda m: ops.build_queue(m, capacity=25))
    ii, jj, nl = f(bm)
    ri, rj, rn = static_queue_order(np.eye(5), capacity=25)
    assert int(nl[0]) == rn
    np.testing.assert_array_equal(np.asarray(ii), ri)
    np.testing.assert_array_equal(np.asarray(jj), rj)


# ---------------------------------------------------------------------------
# hypothesis property suite (dev extra)
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:

    @st.composite
    def _bitmap(draw, max_dim=12):
        mb = draw(st.integers(1, max_dim))
        nb = draw(st.integers(1, max_dim))
        kind = draw(st.sampled_from(["random", "zeros", "ones"]))
        if kind == "zeros":
            return np.zeros((mb, nb), np.int32)
        if kind == "ones":
            return np.ones((mb, nb), np.int32)
        seed = draw(st.integers(0, 2 ** 16))
        dens = draw(st.floats(0.0, 1.0))
        rng = np.random.default_rng(seed)
        return (rng.random((mb, nb)) < dens).astype(np.int32)

    @needs_hypothesis
    @given(_bitmap(), st.sampled_from(["prefix_sum", "argsort"]))
    def test_property_queue_equals_reference_full_capacity(bm, builder):
        _assert_queue_equals_reference(bm, capacity=bm.size, builder=builder)

    @needs_hypothesis
    @given(_bitmap(), st.integers(1, 20),
           st.sampled_from(["prefix_sum", "argsort"]))
    def test_property_queue_equals_reference_any_capacity(bm, cap, builder):
        _assert_queue_equals_reference(bm, capacity=cap, builder=builder)

    @needs_hypothesis
    @given(st.integers(0, 2 ** 16), st.floats(0.0, 1.0),
           st.integers(9, 40), st.integers(9, 40))
    def test_property_compact_matmul_exact_ragged_shapes(seed, dens, m, n):
        """Ragged (non-block-multiple) shapes through the full compact
        path: padding tiles are dead, queue is exact, result == oracle."""
        rng = np.random.default_rng(seed)
        k = 16
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        mask = (rng.random((m, n)) < dens).astype(np.float32)
        mp = jnp.asarray(np.pad(mask, ((0, -m % 8), (0, -n % 8))))
        om = ref.block_any_nonzero(mp, 8, 8)
        got = ops.sparse_gemm(a, b, GemmMasks(out=om),
                              GemmSpec(block=(8, 8, 8), schedule="compact"))
        want = (np.asarray(a) @ np.asarray(b)) * \
            np.asarray(ref.expand_block_mask(om, 8, 8))[:m, :n]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @needs_hypothesis
    @given(_bitmap(max_dim=6), st.integers(0, 2 ** 16))
    def test_property_overflow_fallback_is_bit_exact(bm, seed):
        n_live = int(bm.sum())
        if n_live < 2:
            return                      # cannot under-provision the queue
        cap = n_live - 1                # guaranteed overflow
        mb, nb = bm.shape
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.standard_normal((mb * 8, 8)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((8, nb * 8)), jnp.float32)
        got = ops.sparse_gemm(
            a, b, GemmMasks(out=jnp.asarray(bm)),
            GemmSpec(block=(8, 8, 8), schedule="compact",
                     max_active_blocks=cap))
        predicated = ops.sparse_gemm(a, b, GemmMasks(out=jnp.asarray(bm)),
                                     GemmSpec(block=(8, 8, 8)))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(predicated))
