"""The PR's contract: forward-pass bitmaps are computed ONCE and every
backward mask is *derived* from them — and the derivations are bit-identical
to freshly-computed dense scans (the ``_bitmap_padded`` oracle).

Three property families, as deterministic sweeps:
  1. threaded forward bitmap == dense-scan oracle, for act_matmul and
     relu_conv (stride ∈ {1, 2}, padding ∈ {SAME, VALID});
  2. gradients stay exact vs dense autodiff after the threading refactor
     (incl. the fused σ'-epilogue and its ablation);
  3. the bitmap-op counter: exactly one activation bitmap computation per
     unit per training step, and ZERO standalone gradient scans — dy
     bitmaps are emitted by the producing GEMM's ``bitmap_emit`` epilogue
     (counted ``emit:grad``), with ``scan_pallas:*`` identically zero on
     full CNN and FFN training steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy as pol
from repro.core import sparse_conv
from repro.core.sparse_conv import (
    _im2col, _pad_amounts, _patch_bitmap, _relu_conv_fwd, conv as sconv,
    relu_conv,
)
from repro.core.sparse_linear import (
    _act_matmul_fwd, _bitmap_padded, act_matmul, relu_matmul,
)
from repro.core.sparse_tensor import (
    SparseTensor, coarsen_bitmap, conv_channel_granularity,
    linear_act_granularity,
)
from repro.kernels import stats

PALLAS = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 16, 8))
PALLAS_U = pol.IN_OUT.with_(kernel_impl="pallas", block=(16, 16, 16))

# (channels, block override) beside the C = 5 cases on the policies' own
# tiles: C = 3 and 64 give a patch K (27, 576) that is not a whole number of
# bk = 128 tiles, C = 128 one that is.
WIDE_CHANNELS = [(3, (8, 128, 8)), (64, (8, 128, 8)), (128, (8, 128, 8))]


def _rand(shape, key, sparsify=0.5):
    rng = np.random.default_rng(key)
    x = rng.standard_normal(shape).astype(np.float32)
    if sparsify:
        x *= rng.random(shape) > sparsify
    return jnp.asarray(x)


# ---------------------------------------------------------------------------
# 1. threaded bitmap == freshly-scanned oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [PALLAS, PALLAS_U])
def test_act_matmul_threaded_masks_match_oracle(policy):
    bm, bk, bn = policy.block
    x_pre = _rand((37, 29), 0)
    w = _rand((29, 23), 1, 0.0)
    _, (st, _) = _act_matmul_fwd(x_pre, w, policy, "relu")
    assert st.bitmap is not None
    x = jnp.maximum(x_pre, 0)
    # FP operand mask (bm, bk)
    np.testing.assert_array_equal(
        st.mask_for((bm, bk)), _bitmap_padded(x, bm, bk))
    # BP out_mask (bm, bn) over the σ' footprint == relu footprint
    mult = (x_pre > 0).astype(jnp.float32)
    np.testing.assert_array_equal(
        st.mask_for((bm, bn)), _bitmap_padded(mult, bm, bn))
    # WG transposed operand mask (bm, bk) over Xᵀ
    np.testing.assert_array_equal(
        st.t_mask_for((bm, bk)), _bitmap_padded(x.T, bm, bk))


def _np_block_any(x, b0, b1):
    """Any-nonzero (b0, b1) block bitmap of a 2-D host array, ragged edges
    zero-padded: the fresh-scan oracle, in NumPy so a host callback can run
    it."""
    m, n = x.shape
    mp, np_ = -(-m // b0) * b0, -(-n // b1) * b1
    xp = np.zeros((mp, np_), x.dtype)
    xp[:m, :n] = x
    return (xp.reshape(mp // b0, b0, np_ // b1, b1) != 0).any(axis=(1, 3))


def _spy_gemms(monkeypatch, block):
    """Have every GEMM the conv engine issues check, when it runs, that
    each operand mask it receives equals a fresh scan of that operand.
    Returns (traced, verdicts): per call its operand shapes and which masks
    it had, and per call index the (a_ok, b_ok) found at run time."""
    bm, bk, bn = block
    traced, verdicts = [], {}
    real = sparse_conv._mm

    def spy(a, b, out_mask, a_mask, b_mask, policy, *args, **kw):
        i = len(traced)
        traced.append((a.shape, b.shape, a_mask is not None,
                       b_mask is not None))

        def check(a, b, a_mask, b_mask):
            verdicts[i] = tuple(
                m is None or np.array_equal(np.asarray(m) != 0,
                                            _np_block_any(np.asarray(x),
                                                          *edges))
                for m, x, edges in ((a_mask, a, (bm, bk)),
                                    (b_mask, b, (bk, bn))))

        jax.debug.callback(check, a, b, a_mask, b_mask)
        return real(a, b, out_mask, a_mask, b_mask, policy, *args, **kw)

    monkeypatch.setattr(sparse_conv, "_mm", spy)
    return traced, verdicts


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID"), (2, "VALID")])
@pytest.mark.parametrize(
    "policy,c,block",
    [(PALLAS, 5, None), (PALLAS_U, 5, None)]
    + [(PALLAS, c, block) for c, block in WIDE_CHANNELS])
def test_relu_conv_threaded_masks_match_oracle(stride, padding, policy, c,
                                               block, monkeypatch):
    if block is not None:
        policy = policy.with_(block=block)
    bm, bk, bn = policy.block
    n, h, wd = 2, 9, 11
    x_pre = _rand((n, h, wd, c), 2)
    w = _rand((3, 3, c, 7), 3, 0.0)
    st = jax.jit(lambda x, w: _relu_conv_fwd(x, w, stride, padding,
                                             policy)[1][0])(x_pre, w)
    assert st.bitmap is not None
    x = jnp.maximum(x_pre, 0)
    # out_mask over the (N·H·W, C) σ' footprint
    mask2d = (x_pre > 0).reshape(n * h * wd, c).astype(jnp.float32)
    np.testing.assert_array_equal(
        st.mask_for((bm, bn)), _bitmap_padded(mask2d, bm, bn))
    # patch (im2col) masks vs a fresh scan of the actual patch matrix
    plh = _pad_amounts(h, 3, stride, padding)
    plw = _pad_amounts(wd, 3, stride, padding)
    pad4 = (plh[0], plh[1], plw[0], plw[1])
    pm = _im2col(x, 3, 3, stride, pad4)
    pm = pm.reshape(-1, 3 * 3 * c)
    pb = _patch_bitmap(st, (n, h, wd, c), 3, 3, stride, pad4)
    np.testing.assert_array_equal(
        pb.mask_for((bm, bk)), _bitmap_padded(pm, bm, bk))
    np.testing.assert_array_equal(
        pb.t_mask_for((bm, bk)), _bitmap_padded(pm.T, bm, bk))
    np.testing.assert_array_equal(
        pb.mask_for((bk, bn)), _bitmap_padded(pm, bk, bn))
    # Every operand mask of a two-unit chain's step, the WG stage's dyᵀ
    # (threaded from the upper unit's dX epilogue) and patch matrix P among
    # them, equals a fresh scan of the operand the GEMM receives.
    traced, verdicts = _spy_gemms(monkeypatch, policy.block)
    w2 = _rand((3, 3, 7, 6), 4, 0.0)
    grads = jax.jit(jax.grad(
        lambda x, w, w2: (relu_conv(relu_conv(x, w, stride, padding, policy),
                                    w2, 1, "SAME", policy) ** 2).sum(),
        (0, 1, 2)))(x_pre, w, w2)
    jax.block_until_ready(grads)
    jax.effects_barrier()
    assert len(traced) == 6
    assert verdicts == {i: (True, True) for i in range(6)}
    # the lower unit's WG: dWᵀ = dyᵀ · P, both operands masked
    assert traced[-1] == ((7, pm.shape[0]),
                          (pm.shape[0], -(-9 * c // bn) * bn), True, True)


def test_coarsen_bitmap_is_exact_or_reduce():
    rng = np.random.default_rng(7)
    x = jnp.asarray((rng.random((40, 24)) > 0.8).astype(np.float32))
    fine = _bitmap_padded(x, 2, 4)           # (20, 6) at gran (2, 4)
    np.testing.assert_array_equal(
        coarsen_bitmap(fine, (2, 4), (8, 8)), _bitmap_padded(x, 8, 8))
    # ragged edges: coarsen pads fine bitmap with zeros, oracle pads data
    np.testing.assert_array_equal(
        coarsen_bitmap(fine, (2, 4), (16, 16)), _bitmap_padded(x, 16, 16))


# ---------------------------------------------------------------------------
# 2. gradients stay exact vs dense autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    PALLAS,                                  # compact × fused σ′ epilogue
    PALLAS_U,                                # predicated × fused epilogue
    PALLAS_U.with_(fuse_epilogue=False),     # ablation: separate VPU pass
    PALLAS.with_(fuse_epilogue=False),       # compact × separate VPU pass
    PALLAS.with_(queue_builder="argsort"),   # compact × fused, sort-built q
    pol.IN_OUT,                              # xla_ref threading path
])
def test_act_matmul_grads_exact_after_threading(policy):
    # x_pre continuous (no exact zeros): σ'(0)=0 vs dense-autodiff tie
    # handling is a convention choice, not a threading property.  Negatives
    # give ~50% activation sparsity for the masks to act on.
    x = _rand((37, 29), 10, 0.0)
    w = _rand((29, 23), 11, 0.0)
    ct = _rand((37, 23), 12, 0.7)
    y, vjp = jax.vjp(lambda x, w: relu_matmul(x, w, policy), x, w)
    yd, vjpd = jax.vjp(lambda x, w: jnp.maximum(x, 0) @ w, x, w)
    np.testing.assert_allclose(y, yd, rtol=1e-4, atol=1e-4)
    for g, gd in zip(vjp(ct), vjpd(ct)):
        np.testing.assert_allclose(g, gd, rtol=2e-4, atol=2e-4)
    # masked-out rows of dx are EXACT zeros (losslessness of the epilogue)
    dx = vjp(ct)[0]
    assert np.all(np.asarray(dx)[np.asarray(x) < 0] == 0.0)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID"), (2, "VALID")])
@pytest.mark.parametrize(
    "policy,c,block",
    [(p, 5, None) for p in [PALLAS, PALLAS_U,
                            PALLAS_U.with_(fuse_epilogue=False),
                            PALLAS.with_(fuse_epilogue=False)]]
    + [(p, c, block) for c, block in WIDE_CHANNELS for p in [PALLAS, PALLAS_U]])
def test_relu_conv_grads_exact_after_threading(stride, padding, policy, c,
                                               block):
    if block is not None:
        policy = policy.with_(block=block)
    x = _rand((2, 9, 11, c), 13, 0.0)     # continuous pre-activation
    w = _rand((3, 3, c, 7), 14, 0.0)

    def dense(x, w):
        return jax.lax.conv_general_dilated(
            jnp.maximum(x, 0), w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    f = lambda x, w: (relu_conv(x, w, stride, padding, policy) ** 2).sum()
    g = lambda x, w: (dense(x, w) ** 2).sum()
    (fa, ga), (fb, gb) = (jax.jit(jax.value_and_grad(h, (0, 1)))(x, w)
                          for h in (f, g))
    np.testing.assert_allclose(fa, fb, rtol=1e-4)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("queue_builder", ["prefix_sum", "argsort"])
def test_compact_epilogue_bounded_queue_grads_exact(queue_builder):
    """The compact×epilogue cell with a REAL queue bound: the fused σ′
    writeback must stay exact when the schedule is the compacted queue at
    exactly-live capacity (the WDU case) — for both queue builders."""
    from repro.kernels import ops as kops, ref as kref
    rng = np.random.default_rng(31)
    dy = jnp.asarray(rng.standard_normal((40, 24)), jnp.float32)
    w_t = jnp.asarray(rng.standard_normal((24, 48)), jnp.float32)
    relu_mask = jnp.asarray(rng.random((40, 48)) > 0.6, jnp.float32)
    mask_p = jnp.pad(relu_mask, ((0, 0), (0, 0)))
    n_live = int(np.asarray(kref.block_any_nonzero(mask_p, 8, 16)).sum())
    spec = kops.GemmSpec(block=(8, 8, 16), schedule="compact",
                         max_active_blocks=n_live,
                         queue_builder=queue_builder, epilogue="sigma_prime")
    got = kops.sparse_gemm(
        dy, w_t, kops.GemmMasks(out=kref.block_any_nonzero(mask_p, 8, 16)),
        spec, epilogue_mult=relu_mask)
    want = kref.relu_bwd_masked(dy, w_t, relu_mask, bm=8, bk=8, bn=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # fused-epilogue zeros are exact zeros even through the scatter-back
    assert np.all(np.asarray(got)[np.asarray(relu_mask) == 0] == 0.0)


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "VALID")])
def test_plain_conv_grads_exact_after_threading(stride, padding):
    policy = PALLAS_U
    x = _rand((2, 8, 8, 4), 15, 0.0)         # signed input (post-pool case)
    w = _rand((3, 3, 4, 6), 16, 0.0)

    def dense(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    f = lambda x, w: (sconv(x, w, stride, padding, policy) ** 2).sum()
    g = lambda x, w: (dense(x, w) ** 2).sum()
    np.testing.assert_allclose(f(x, w), g(x, w), rtol=1e-4)
    for a, b in zip(jax.grad(f, (0, 1))(x, w), jax.grad(g, (0, 1))(x, w)):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# 3. the audit property: one bitmap computation per tensor per step
# ---------------------------------------------------------------------------

def _grad_eagerly(f, *args):
    return jax.grad(f, tuple(range(len(args))))(*args)


def test_act_matmul_one_bitmap_op_per_step():
    x = _rand((37, 29), 20)
    w = _rand((29, 23), 21, 0.0)
    stats.reset()
    _grad_eagerly(lambda x, w: (act_matmul(x, w, PALLAS, "relu") ** 2).sum(),
                  x, w)
    assert stats.total("act") == 1, stats.counts()   # fused fwd encode only
    assert stats.total("grad") == 1, stats.counts()  # one dy scan, 2 masks


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "VALID")])
def test_relu_conv_one_bitmap_op_per_step(stride, padding):
    x = _rand((2, 9, 11, 5), 22)
    w = _rand((3, 3, 5, 7), 23, 0.0)
    stats.reset()
    _grad_eagerly(
        lambda x, w: (relu_conv(x, w, stride, padding, PALLAS) ** 2).sum(),
        x, w)
    assert stats.total("act") == 1, stats.counts()
    assert stats.total("grad") == 1, stats.counts()


def test_depthwise_threaded_masks_match_oracle():
    """Per-group masks are column slices of the ONE bitmap: group g's slice
    of the im2col'd bitmap equals a fresh scan of group g's im2col'd data
    (the group-boundary granularity contract makes the slice exact)."""
    from repro.core.policy import grouped_gemm_block
    from repro.core.sparse_conv import (
        _conv_engine_fwd, _group_patches,
    )

    policy = PALLAS_U
    n, h, wd, c, groups = 2, 9, 11, 6, 2
    r = s = 3
    x_pre = _rand((n, h, wd, c), 40)
    w = _rand((3, 3, c // groups, 8), 41, 0.0)
    _, (st, _) = _conv_engine_fwd(x_pre, w, 1, "SAME", policy, True, groups)
    assert st.bitmap is not None
    gc = st.gran[1]
    x = jnp.maximum(x_pre, 0)
    plh = _pad_amounts(h, r, 1, "SAME")
    plw = _pad_amounts(wd, s, 1, "SAME")
    pad4 = (plh[0], plh[1], plw[0], plw[1])
    pm = _im2col(x, r, s, 1, pad4).reshape(-1, r * s * c)
    cg = c // groups
    blk = grouped_gemm_block(policy, (pm.shape[0], r * s * cg, 4), (1, gc, 1))
    pb = _patch_bitmap(st, (n, h, wd, c), r, s, 1, pad4)
    pbg = _group_patches(pb.bitmap, r * s, groups)
    derived = coarsen_bitmap(pbg, (1, gc), (blk[0], blk[1]))
    data_g = _group_patches(pm, r * s, groups)
    for g in range(groups):
        np.testing.assert_array_equal(
            derived[g], _bitmap_padded(data_g[g], blk[0], blk[1]))
    # per-group out_mask == fresh scan of the group's σ' column slice
    from repro.core.sparse_conv import _group_cols
    mask2d = (x_pre > 0).reshape(n * h * wd, c).astype(jnp.float32)
    om = coarsen_bitmap(_group_cols(st.bitmap, groups), (1, gc),
                        (blk[0], blk[2]))
    mg = _group_cols(mask2d, groups)
    for g in range(groups):
        np.testing.assert_array_equal(
            om[g], _bitmap_padded(mg[g], blk[0], blk[2]))


def test_depthwise_pw_chain_one_bitmap_per_activation():
    """dw→pw chain (the MobileNet block): each activation is encoded ONCE
    per step, each gradient scanned at most once — the per-activation
    budget holds across the depthwise boundary too."""
    from repro.core.sparse_conv import depthwise_relu_conv

    c = 8
    x = _rand((2, 8, 8, c), 42)
    wdw = _rand((3, 3, 1, c), 43, 0.0)
    wpw = _rand((1, 1, c, 12), 44, 0.0)

    def chain(x, wdw, wpw):
        y = depthwise_relu_conv(x, wdw, 1, "SAME", PALLAS)
        return (relu_conv(y, wpw, 1, "SAME", PALLAS) ** 2).sum()

    stats.reset()
    _grad_eagerly(chain, x, wdw, wpw)
    # two fused units (dw, pw) ⇒ two act encodes, two grad scans — exactly
    assert stats.total("act") == 2, stats.counts()
    assert stats.total("grad") == 2, stats.counts()
    assert stats.counts().get("conv:dense_fallback", 0) == 0


def _scan_ops(counts):
    """All standalone bitmap-scan launches, pallas and xla_ref alike."""
    return sum(v for k, v in counts.items()
               if k.startswith("scan_pallas:") or k.startswith("scan:"))


def test_pallas_scan_bitmap_is_opt_in_for_raw_inputs():
    """Standalone ``kernels.bitmap_scan`` survives ONLY as the opt-in entry
    scan of raw signed model inputs (``scan_signed_inputs=True``) — counted
    as ``scan_pallas:*``, with the XLA-reference ``scan:*`` key silent.
    Gradients never scan on any policy: dy bitmaps come from the producing
    GEMM's ``bitmap_emit`` epilogue (or a registry miss ⇒ no mask)."""
    x = _rand((2, 8, 8, 4), 45)
    w = _rand((3, 3, 4, 6), 46, 0.0)
    scanning = PALLAS.with_(scan_signed_inputs=True)
    stats.reset()
    _grad_eagerly(
        lambda x, w: (sconv(x, w, 1, "SAME", scanning) ** 2).sum(), x, w)
    c = stats.counts()
    assert c.get("scan_pallas:act", 0) == 1, c
    assert c.get("scan_pallas:grad", 0) == 0, c      # dy is never scanned
    assert c.get("scan:act", 0) == 0 and c.get("scan:grad", 0) == 0, c
    # default policy: NO standalone scan anywhere — the hot path is
    # scan-free and the dx GEMM emits its own bitmap at writeback
    stats.reset()
    _grad_eagerly(
        lambda x, w: (sconv(x, w, 1, "SAME", PALLAS) ** 2).sum(), x, w)
    c = stats.counts()
    assert _scan_ops(c) == 0, c
    assert c.get("emit:grad", 0) >= 1, c


def test_cnn_training_step_is_scan_free():
    """Full jitted CNN training step (vgg16 smoke geometry): every dy
    bitmap is emitted by the producing GEMM's epilogue, so ``scan_pallas:*``
    is identically zero in the step's traced graph — the tentpole claim."""
    from repro.models.cnn import build_cnn

    model = build_cnn("vgg16", image_size=8, width=0.0625, num_classes=10)
    params = model.init(jax.random.key(0))
    img = jax.random.normal(jax.random.key(1), (1, 8, 8, 3), jnp.float32)
    lbl = jax.random.randint(jax.random.key(2), (1,), 0, 10)

    @jax.jit
    def step(p, img, lbl):
        loss, g = jax.value_and_grad(
            lambda q: model.loss(q, img, lbl, PALLAS))(p)
        return jax.tree.map(lambda w, dw: w - 0.05 * dw, p, g), loss

    stats.reset()
    new_p, loss = step(params, img, lbl)
    jax.block_until_ready(loss)
    c = stats.counts()
    assert _scan_ops(c) == 0, c
    assert c.get("emit:grad", 0) >= 1, c             # epilogue is producing
    assert stats.total("act") >= 1, c                # fused encodes intact
    assert bool(np.isfinite(np.asarray(loss)))


def test_ffn_training_step_is_scan_free():
    """Full jitted FFN (relu) training step: the down-projection's backward
    dX GEMM emits the hidden gradient's bitmap; the up-projection's backward
    consumes it via the registry — zero standalone scans end to end."""
    from repro.models.ffn import FFNConfig, ffn_apply, ffn_init

    cfg = FFNConfig(d_model=16, d_ff=32, activation="relu",
                    sparse_policy=PALLAS)
    params = ffn_init(jax.random.key(10), cfg)
    x = jax.random.normal(jax.random.key(11), (32, 16), jnp.float32)
    y = jax.random.normal(jax.random.key(12), (32, 16), jnp.float32)

    @jax.jit
    def step(p, x, y):
        loss, g = jax.value_and_grad(
            lambda q: jnp.mean((ffn_apply(q, x, cfg) - y) ** 2))(p)
        return jax.tree.map(lambda w, dw: w - 0.05 * dw, p, g), loss

    stats.reset()
    new_p, loss = step(params, x, y)
    jax.block_until_ready(loss)
    c = stats.counts()
    assert _scan_ops(c) == 0, c
    assert c.get("emit:grad", 0) >= 1, c
    assert bool(np.isfinite(np.asarray(loss)))


def test_dc_policy_computes_no_bitmaps():
    x = _rand((16, 16), 24)
    w = _rand((16, 8), 25, 0.0)
    stats.reset()
    _grad_eagerly(lambda x, w: (act_matmul(x, w, pol.DC, "relu") ** 2).sum(),
                  x, w)
    # no bitmap computations, no queue builds — only the dispatcher's
    # normalized gemm:dense launch keys (fwd + dx + dw = 3)
    assert stats.total("act") == 0 and stats.total("grad") == 0, stats.counts()
    assert stats.queue_builds() == 0, stats.counts()
    assert stats.gemm_launches(schedule="dense", groups=1) == 3, stats.counts()
    assert stats.gemm_launches() == stats.total() == 3, stats.counts()


def test_granularity_helpers_divide_all_consumers():
    for block in [(8, 16, 8), (16, 16, 16), (128, 128, 128), (16, 8, 32)]:
        bm, bk, bn = block
        gr, gc = linear_act_granularity(block)
        assert bm % gr == 0 and bk % gr == 0          # rows + transposed cols
        assert bk % gc == 0 and bn % gc == 0 and bm % gc == 0
        for ch in (5, 16, 64, 384):
            g = conv_channel_granularity(ch, block)
            assert ch % g == 0 and bm % g == 0 and bk % g == 0 and bn % g == 0
