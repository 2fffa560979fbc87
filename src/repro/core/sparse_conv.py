"""The paper's fused CONV–ReLU unit, lowered to masked GEMMs via im2col.

The paper's accelerator executes CONV as GEMM over the receptive field
(K = C·R·S — its "synapse blocking at 1024" is K-blocking, §4.4).  We do the
same: im2col the operand, run the block-sparse GEMM kernels, fold back.

ONE engine, four public faces.  ``_conv_engine_fwd``/``_conv_engine_bwd``
is a single parameterized custom-VJP pair taking ``(fused_relu, groups)``;
every conv flavour is a thin wrapper over it:

  relu_conv            fused_relu=True,  groups=1   (the paper's unit)
  conv                 fused_relu=False, groups=1   (signed input: pool /
                                                     input-layer boundary)
  grouped variants     groups=G (C % G == 0, M % G == 0): per-group im2col
                       → ONE batched masked GEMM (G, ·, ·) per stage
  depthwise_relu_conv  groups=C — MobileNet's dw layers, full FP/BP/WG
                       sparsity treatment instead of a dense fallback

All three stages realize the same skipping opportunities as
core.sparse_linear:
  FP  input sparsity of relu(x_pre) patches,
  BP  output sparsity from σ'(x_pre) (survives BatchNorm *after* the conv),
      + input sparsity of the incoming gradient patches,
  WG  input sparsity on both operands.

Sparsity metadata lifecycle: the forward pass runs the fused
``kernels.relu_encode`` over the activation's (N·H·W, C) view ONCE, at
per-pixel row granularity so the bitmap stays spatially addressable.  Every
other mask is then *derived* from it without rescanning tensor-sized data:

  * the backward out_mask is the same bitmap re-tiled to (bm, bn) — the
    paper's FP/BP footprint identity;
  * patch (im2col) operand masks — FP a_mask and the WG Xᵀ mask — come from
    running ``_im2col`` on the BITMAP itself (a gather over an array C/gc×
    smaller than the activation), then coarsening.  This is exact, because
    an im2col'd any-nonzero cell equals the any-nonzero of the im2col'd
    data (same gather, zero padding on both sides);
  * the incoming gradient is scanned at most once per step; its dilated/
    im2col'd mask (dX GEMM) and its (bk, bn) re-tiling (dW GEMM) are both
    derived from that single fine bitmap.

Grouped convs reuse the SAME derivations: the channel granularity divides
C//G (see ``conv_channel_granularity``), so per-group masks are pure
reshapes of the one bitmap's columns — group g's slice of the im2col'd
bitmap IS the bitmap of group g's im2col'd data.  Per-group GEMM tiles
come from ``policy.gemm_spec(dims=..., grans=...)`` (the
``grouped_gemm_block`` degenerate-tile rule): depthwise K-dims are tiny
(R·S·1), so edges degenerate to the granularity-rounded dims instead of
padding a 128-block that could never mask anything.  Every stage's GEMM —
dense or grouped — is one ``kernels.ops.sparse_gemm`` dispatch on that
spec (see docs/gemm_api.md).

Exactness vs dense autodiff is asserted in tests for stride ∈ {1, 2},
padding ∈ {SAME, VALID} and groups ∈ {1, 2, C}; threaded-vs-rescanned mask
equality is property-tested in tests/test_bitmap_threading.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import stats
from repro.kernels.shapes import block_bitmap as _bitmap_padded
from .policy import SparsityPolicy
from .sparse_linear import _mm, _needs_act_bitmap, _needs_grad_bitmap
from .sparse_tensor import (
    SparseTensor,
    coarsen_bitmap,
    conv_channel_granularity,
    lookup_grad_bitmap,
    register_grad_bitmap,
    scan_bitmap,
)


def _pad_amounts(h: int, r: int, stride: int, padding: str) -> Tuple[int, int]:
    if padding == "VALID":
        return 0, 0
    out = -(-h // stride)  # ceil
    total = max((out - 1) * stride + r - h, 0)
    return total // 2, total - total // 2


def conv_out_size(h: int, r: int, stride: int, padding: str) -> int:
    lo, hi = _pad_amounts(h, r, stride, padding)
    return (h + lo + hi - r) // stride + 1


def _im2col(x: jnp.ndarray, r: int, s: int, stride: int,
            pad: Tuple[int, int, int, int]) -> jnp.ndarray:
    """x: (N,H,W,C) -> (N, U, V, R*S*C) patches, (r, s, c)-ordered."""
    n, h, w, c = x.shape
    plo_h, phi_h, plo_w, phi_w = pad
    xp = jnp.pad(x, ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0)))
    hp, wp = h + plo_h + phi_h, w + plo_w + phi_w
    u = (hp - r) // stride + 1
    v = (wp - s) // stride + 1
    cols = []
    for dr in range(r):
        for ds in range(s):
            cols.append(
                jax.lax.slice(
                    xp, (0, dr, ds, 0),
                    (n, dr + (u - 1) * stride + 1, ds + (v - 1) * stride + 1, c),
                    (1, stride, stride, 1),
                )
            )
    patches = jnp.stack(cols, axis=3)          # (N,U,V,R*S,C)
    return patches.reshape(n, u, v, r * s * c)


def _dilate_hw(x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Insert stride-1 zeros between spatial elements (for grad-input)."""
    if stride == 1:
        return x
    n, h, w, c = x.shape
    out = jnp.zeros((n, (h - 1) * stride + 1, (w - 1) * stride + 1, c), x.dtype)
    return out.at[:, ::stride, ::stride, :].set(x)


# ---------------------------------------------------------------------------
# Group splitting — pure reshapes; the (tap, channel)-minor K ordering means
# group g's columns are contiguous per tap, so one transpose regroups a full
# patch matrix (data OR bitmap) into the (G, ·, ·) batched-GEMM layout.
# ---------------------------------------------------------------------------

def _group_patches(pm2: jnp.ndarray, taps: int, groups: int) -> jnp.ndarray:
    """(T, taps*C') patch matrix -> (G, T, taps*C'/G), per-group K slices.

    Works identically on data (C' = C) and fine bitmaps (C' = C/gc): the
    granularity divides C//G, so cells nest inside groups."""
    t, k = pm2.shape
    cg = k // taps // groups
    return pm2.reshape(t, taps, groups, cg).transpose(2, 0, 1, 3) \
        .reshape(groups, t, taps * cg)


def _group_cols(x2: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(T, C') channel-minor matrix -> (G, T, C'/G)."""
    t, c = x2.shape
    return x2.reshape(t, groups, c // groups).transpose(1, 0, 2)


def _ungroup_cols(x3: jnp.ndarray) -> jnp.ndarray:
    """(G, T, C/G) -> (T, C), inverse of ``_group_cols``."""
    g, t, cg = x3.shape
    return x3.transpose(1, 0, 2).reshape(t, g * cg)


def _group_weights(w: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(R, S, C//G, M) grouped-HWIO weights -> (G, R·S·C//G, M//G).

    Follows lax.conv_general_dilated's feature_group_count convention:
    output block g (channels [g·M/G, (g+1)·M/G)) reads input group g."""
    r, s, cg, m = w.shape
    mg = m // groups
    return w.reshape(r * s * cg, groups, mg).transpose(1, 0, 2)


def _group_weights_bwd(w: jnp.ndarray, groups: int) -> jnp.ndarray:
    """Per-group dX weights: (R, S, C//G, M) -> (G, R·S·M//G, C//G),
    spatially flipped and (r, s, m, c)-ordered to match gradient patches."""
    r, s, cg, m = w.shape
    mg = m // groups
    wf = jnp.flip(w, axis=(0, 1)).reshape(r, s, cg, groups, mg)
    return wf.transpose(3, 0, 1, 4, 2).reshape(groups, r * s * mg, cg)


# ---------------------------------------------------------------------------
# Bitmap derivation (no tensor-sized scans past this line)
# ---------------------------------------------------------------------------

def _patch_bitmap(st: SparseTensor, spatial: Tuple[int, int, int, int],
                  r: int, s: int, stride: int,
                  pad: Tuple[int, int, int, int]) -> SparseTensor:
    """im2col in bitmap space: (N·H·W, C/gc) fine bitmap -> fine bitmap of
    the patch matrix (N·U·V, R·S·C/gc), exactly matching a fresh scan of
    ``_im2col(data)``.  Pure gather on the bitmap — the activation is not
    touched."""
    n, h, w, c = spatial
    gc = st.gran[1]
    with stats.lifecycle_scope("derive", "im2col"):
        fb4 = st.bitmap.reshape(n, h, w, c // gc)
        pb = _im2col(fb4, r, s, stride, pad)   # (N, U, V, R*S*C/gc)
        u, v = pb.shape[1], pb.shape[2]
        return SparseTensor(None, pb.reshape(n * u * v, -1), (1, gc))


def _encode_conv_act(x_pre: jnp.ndarray, policy: SparsityPolicy,
                     gc: int) -> Tuple[jnp.ndarray, SparseTensor]:
    """(relu(x_pre), SparseTensor over the (N·H·W, C) view) — ONE fused
    encode (pallas) or one counted scan (xla_ref) per activation per step."""
    n, h, w, c = x_pre.shape
    with stats.lifecycle_scope("layout", "fp"):
        x2d = x_pre.reshape(n * h * w, c)
    if policy.kernel_impl == "pallas":
        y2d, fb = kops.relu_encode(x2d, block=(1, gc),
                                   interpret=policy.interpret)
        with stats.lifecycle_scope("layout", "fp"):
            x = y2d.reshape(n, h, w, c)
    else:
        x = jnp.maximum(x_pre, jnp.zeros((), x_pre.dtype))
        fb = scan_bitmap(x.reshape(n * h * w, c), (1, gc), kind="act")
    return x, SparseTensor(x_pre, fb, (1, gc))


def _grad_sparse_tensor(dy, dy32: jnp.ndarray, policy: SparsityPolicy,
                        m: int, groups: int = 1) -> SparseTensor:
    """Fine bitmap of the incoming gradient, recovered from the PRODUCING
    dX GEMM's writeback-emitted bitmap (registered against the exact
    cotangent object ``dy``) — never a rescan.  A miss (cotangent straight
    from the loss / a pool / BatchNorm, or an unusable granularity)
    degrades to no dy mask: skipping lost, numerics untouched."""
    if not _needs_grad_bitmap(policy):
        return SparseTensor(dy32, None, None)
    hit = lookup_grad_bitmap(dy)
    if hit is None:
        return SparseTensor(dy32, None, None)
    fb, (gr, gcg) = hit
    bm, bk, bn = policy.block
    # The conv derivations need per-pixel rows (the bitmap is reshaped to
    # the (N, U, V, M/gc) spatial view), channel cells nesting inside
    # groups, and a channel granularity every derived mask edge divides.
    if (gr != 1 or m % gcg or (m // gcg) % groups
            or bk % gcg or bn % gcg):
        return SparseTensor(dy32, None, None)
    return SparseTensor(dy32, fb, (1, gcg))


# ---------------------------------------------------------------------------
# The engine — one forward/backward pair for every conv flavour
# ---------------------------------------------------------------------------
#
# Each stage's data-side re-layout (im2col, dy dilation, weight flips and
# transposes, the σ′ multiplier, group and output reshapes) runs under a
# ``repro:layout:<fp|bp|wg>`` scope, opened between the derive and gemm
# scopes and never around them, so a profile reads the layout work apart
# from the sparse engine's own.

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _conv_engine(x: jnp.ndarray, w: jnp.ndarray, stride: int, padding: str,
                 policy: SparsityPolicy, fused_relu: bool, groups: int):
    """y = conv2d(relu(x) if fused_relu else x, w, groups).

    x: (N,H,W,C); w: (R,S,C//G,M) — lax grouped-HWIO layout."""
    y, _ = _conv_engine_fwd(x, w, stride, padding, policy, fused_relu, groups)
    return y


def _conv_engine_fwd(x_in, w, stride, padding, policy: SparsityPolicy,
                     fused_relu: bool, groups: int):
    n, h, wd, c = x_in.shape
    r, s, cg_w, m = w.shape
    assert c % groups == 0 and m % groups == 0 and cg_w == c // groups, \
        (x_in.shape, w.shape, groups)
    plh = _pad_amounts(h, r, stride, padding)
    plw = _pad_amounts(wd, s, stride, padding)
    pad4 = (plh[0], plh[1], plw[0], plw[1])

    # --- activation + its once-computed bitmap ---
    if fused_relu:
        if _needs_act_bitmap(policy):
            gc = conv_channel_granularity(c, policy.block, groups)
            x, st = _encode_conv_act(x_in, policy, gc)
        else:
            x = jnp.maximum(x_in, jnp.zeros((), x_in.dtype))
            st = SparseTensor(x_in, None, None)
    else:
        # Signed input (pool / input-layer boundary): no fused encode, so a
        # bitmap costs a standalone scan — opt-in via scan_signed_inputs
        # (off by default: raw inputs are near-dense, and with dy bitmaps
        # emitted by the GEMM epilogue the hot path then launches zero
        # scan_pallas:* passes).
        x = x_in
        st = SparseTensor(x, None, None)
        if policy.scan_signed_inputs and policy.kernel_impl == "pallas" and (
                policy.use_input_sparsity_fp or policy.use_input_sparsity_bp):
            gc = conv_channel_granularity(c, policy.block, groups)
            st = SparseTensor(
                x,
                scan_bitmap(x.reshape(n * h * wd, c), (1, gc), kind="act",
                            impl=policy.kernel_impl,
                            interpret=policy.interpret),
                (1, gc))

    # --- FP GEMM: patches @ weights ---
    with stats.lifecycle_scope("layout", "fp"):
        patches = _im2col(x, r, s, stride, pad4)
        u, v = patches.shape[1], patches.shape[2]
        t = n * u * v
        pm = patches.reshape(t, r * s * c)
    want_a_mask = (policy.use_input_sparsity_fp
                   and policy.kernel_impl == "pallas"
                   and st.bitmap is not None)
    if groups == 1:
        a_mask = None
        if want_a_mask:
            bm, bk, bn = policy.block
            a_mask = _patch_bitmap(st, (n, h, wd, c), r, s, stride, pad4) \
                .mask_for((bm, bk))
        with stats.lifecycle_scope("layout", "fp"):
            w2 = w.reshape(r * s * c, m)
        y = _mm(pm, w2, None, a_mask, None, policy, x_in.dtype)
    else:
        cg, mg = c // groups, m // groups
        gc = st.gran[1] if st.gran else 1
        spec = policy.gemm_spec(groups=groups, dims=(t, r * s * cg, mg),
                                grans=(1, gc, 1))
        blk = spec.block
        a_mask = None
        if want_a_mask and r * s * cg >= policy.grouped_sparsity_min_k:
            pb = _patch_bitmap(st, (n, h, wd, c), r, s, stride, pad4)
            pbg = _group_patches(pb.bitmap, r * s, groups)
            a_mask = coarsen_bitmap(pbg, (1, gc), (blk[0], blk[1]))
        with stats.lifecycle_scope("layout", "fp"):
            pmg = _group_patches(pm, r * s, groups)
            wg = _group_weights(w, groups)
        yg = _mm(pmg, wg, None, a_mask, None, policy, x_in.dtype, spec=spec)
        with stats.lifecycle_scope("layout", "fp"):
            y = _ungroup_cols(yg)
    with stats.lifecycle_scope("layout", "fp"):
        y = y.reshape(n, u, v, m)
    return y, (st, w)


def _conv_engine_bwd(stride, padding, policy: SparsityPolicy,
                     fused_relu: bool, groups: int, res, dy):
    st, w = res
    n, h, wd, c = st.data.shape
    r, s, _, m = w.shape
    u, v = dy.shape[1], dy.shape[2]
    bm, bk, bn = policy.block
    with stats.lifecycle_scope("layout", "bp"):
        if fused_relu:
            x_pre = st.data
            relu_mask = (x_pre > 0)
            x = jnp.where(relu_mask, x_pre, jnp.zeros((), x_pre.dtype))
            out_dtype = x_pre.dtype
        else:
            x = st.data
            relu_mask = None
            out_dtype = x.dtype
        dy32 = dy.astype(jnp.float32)
    st_dy = _grad_sparse_tensor(dy, dy32, policy, m, groups)
    t = n * u * v
    cg, mg = c // groups, m // groups
    gc = st.gran[1] if st.gran else 1
    gcg = st_dy.gran[1] if st_dy.gran else 1

    # ---- dX: full-correlation of dilated dy with flipped w; for the fused
    # unit the σ' Hadamard rides the kernel epilogue → OUTPUT sparsity on
    # the (N·H·W, C) GEMM. ----
    plh = _pad_amounts(h, r, stride, padding)
    plw = _pad_amounts(wd, s, stride, padding)
    with stats.lifecycle_scope("layout", "bp"):
        dyd = _dilate_hw(dy32, stride)
        hd, wdd = dyd.shape[1], dyd.shape[2]
        # output spatial size must equal (h, wd):  pad_lo = r-1-fwd_pad_lo
        pg_h_lo = r - 1 - plh[0]
        pg_h_hi = h - (hd + pg_h_lo - r + 1)
        pg_w_lo = s - 1 - plw[0]
        pg_w_hi = wd - (wdd + pg_w_lo - s + 1)
        gpad4 = (pg_h_lo, pg_h_hi, pg_w_lo, pg_w_hi)
        gpatches = _im2col(dyd, r, s, 1, gpad4)
        gm2 = gpatches.reshape(n * h * wd, r * s * m)
    # out_mask: the forward ReLU bitmap, re-tiled (footprint(σ') ==
    # footprint(relu) — paper §3.2).  Zero recompute.  Plain convs have no
    # σ' ⇒ no output sparsity (Fig. 11 discussion).
    use_out = fused_relu and policy.use_output_sparsity \
        and st.bitmap is not None
    # gradient-patch mask: the dy bitmap dilated and im2col'd in bitmap
    # space — mirrors exactly what the data underwent.
    gpb2 = None
    if st_dy.bitmap is not None:
        with stats.lifecycle_scope("derive", "grad_patches"):
            gfb4 = st_dy.bitmap.reshape(n, u, v, m // gcg)
            gpb = _im2col(_dilate_hw(gfb4, stride), r, s, 1, gpad4)
            gpb2 = gpb.reshape(n * h * wd, -1)
    with stats.lifecycle_scope("layout", "bp"):
        mask2d = relu_mask.reshape(n * h * wd, c).astype(jnp.float32) \
            if fused_relu else None

    # This dX GEMM produces the layer BELOW's dy: its writeback epilogue
    # emits that dy's fine bitmap (per-pixel rows, channel granularity of
    # THIS layer's input) and registers it against the returned cotangent.
    emit_gc = conv_channel_granularity(c, policy.block, groups) \
        if _needs_grad_bitmap(policy) else None

    if groups == 1:
        with stats.lifecycle_scope("layout", "bp"):
            wt = jnp.flip(w, axis=(0, 1)).transpose(0, 1, 3, 2) \
                .reshape(r * s * m, c)
        out_mask = st.mask_for((bm, bn)) if use_out else None
        g_mask = None
        if gpb2 is not None:
            g_mask = coarsen_bitmap(gpb2, (1, gcg), (bm, bk))
        with stats.lifecycle_scope("layout", "bp"):
            wt = wt.astype(jnp.float32)
        res_dx = _mm(gm2, wt, out_mask, g_mask, None,
                     policy, out_dtype, epilogue=mask2d,
                     emit_gran=None if emit_gc is None else (1, emit_gc))
        dx2, dx_bits = res_dx if emit_gc is not None else (res_dx, None)
        with stats.lifecycle_scope("layout", "bp"):
            dx = dx2.reshape(n, h, wd, c)
        if emit_gc is not None:
            register_grad_bitmap(dx, dx_bits, (1, emit_gc))
    else:
        spec = policy.gemm_spec(groups=groups,
                                dims=(n * h * wd, r * s * mg, cg),
                                grans=(1, gcg, gc))
        blk = spec.block
        out_mask = None
        if use_out:
            out_mask = coarsen_bitmap(_group_cols(st.bitmap, groups),
                                      (1, gc), (blk[0], blk[2]))
        g_mask = None
        if gpb2 is not None and r * s * mg >= policy.grouped_sparsity_min_k:
            g_mask = coarsen_bitmap(_group_patches(gpb2, r * s, groups),
                                    (1, gcg), (blk[0], blk[1]))
        with stats.lifecycle_scope("layout", "bp"):
            epi = _group_cols(mask2d, groups) if mask2d is not None \
                else None
            gmg = _group_patches(gm2, r * s, groups)
            wbg = _group_weights_bwd(w, groups).astype(jnp.float32)
        res_dx = _mm(gmg, wbg, out_mask, g_mask, None, policy, out_dtype,
                     epilogue=epi, spec=spec,
                     emit_gran=None if emit_gc is None else (1, emit_gc))
        dxg, dxg_bits = res_dx if emit_gc is not None else (res_dx, None)
        with stats.lifecycle_scope("layout", "bp"):
            dx = _ungroup_cols(dxg).reshape(n, h, wd, c)
        if emit_gc is not None and dxg_bits is not None:
            # Per-group bits columns regroup to the full channel axis the
            # same way the data does (cells nest inside groups: gc | C/G).
            register_grad_bitmap(dx, _ungroup_cols(dxg_bits), (1, emit_gc))

    # ---- dW = patches(x)ᵀ @ dy — WG stage, input sparsity both sides ----
    pad4 = (plh[0], plh[1], plw[0], plw[1])
    with stats.lifecycle_scope("layout", "wg"):
        patches = _im2col(x, r, s, stride, pad4)
        pm = patches.reshape(t, r * s * c).astype(jnp.float32)
        dym = dy32.reshape(t, m)
    want_pt_mask = _needs_grad_bitmap(policy) and st.bitmap is not None
    if groups == 1:
        pt_mask = None
        if want_pt_mask:
            # Xᵀ patch mask: forward bitmap -> patch bitmap -> block transp.
            pt_mask = _patch_bitmap(st, (n, h, wd, c), r, s, stride, pad4) \
                .t_mask_for((bm, bk))
        dym_mask = st_dy.mask_for((bk, bn))
        with stats.lifecycle_scope("layout", "wg"):
            pmt = pm.T
        dw = _mm(pmt, dym, None, pt_mask, dym_mask, policy, jnp.float32)
        with stats.lifecycle_scope("layout", "wg"):
            dw = dw.reshape(r, s, c, m)
    else:
        spec = policy.gemm_spec(groups=groups, dims=(r * s * cg, t, mg),
                                grans=(gc, 1, gcg))
        blk = spec.block
        pt_mask = None
        if want_pt_mask:
            pb = _patch_bitmap(st, (n, h, wd, c), r, s, stride, pad4)
            pbg = _group_patches(pb.bitmap, r * s, groups)
            pt_mask = coarsen_bitmap(pbg.transpose(0, 2, 1), (gc, 1),
                                     (blk[0], blk[1]))
        dym_mask = None
        if st_dy.bitmap is not None:
            dym_mask = coarsen_bitmap(_group_cols(st_dy.bitmap, groups),
                                      (1, gcg), (blk[1], blk[2]))
        with stats.lifecycle_scope("layout", "wg"):
            pmg = _group_patches(pm, r * s, groups).transpose(0, 2, 1)
            dymg = _group_cols(dym, groups)
        dwg = _mm(pmg, dymg, None, pt_mask, dym_mask, policy, jnp.float32,
                  spec=spec)
        # (G, R·S·C//G, M//G) -> (R, S, C//G, M) group-major output channels
        with stats.lifecycle_scope("layout", "wg"):
            dw = dwg.transpose(1, 0, 2).reshape(r, s, cg, m)
    with stats.lifecycle_scope("layout", "wg"):
        dw = dw.astype(w.dtype)
    return dx, dw


_conv_engine.defvjp(_conv_engine_fwd, _conv_engine_bwd)


# ---------------------------------------------------------------------------
# Public wrappers — thin faces over the one engine
# ---------------------------------------------------------------------------

def relu_conv(x_pre: jnp.ndarray, w: jnp.ndarray, stride: int, padding: str,
              policy: SparsityPolicy, groups: int = 1):
    """y = conv2d(relu(x_pre), w). x_pre: (N,H,W,C); w: (R,S,C//G,M)."""
    return _conv_engine(x_pre, w, stride, padding, policy, True, groups)


def conv(x: jnp.ndarray, w: jnp.ndarray, stride: int, padding: str,
         policy: SparsityPolicy, groups: int = 1):
    """Plain conv2d (no fused ReLU): FP/BP input sparsity only.

    Used at MaxPool→CONV and input-layer boundaries where the paper notes
    output sparsity is not applicable (Fig. 11 discussion).  The input's
    nonzero bitmap is still computed only once (one counted scan — x may be
    signed, so the fused ReLU encode does not apply) and threaded to the
    forward operand mask and the WG transposed mask.
    """
    return _conv_engine(x, w, stride, padding, policy, False, groups)


def depthwise_relu_conv(x_pre: jnp.ndarray, w: jnp.ndarray, stride: int,
                        padding: str, policy: SparsityPolicy):
    """Depthwise conv over relu(x_pre): groups == C, w: (R,S,1,C·mult).

    MobileNet's dw layers — each channel is its own group, so the engine
    runs C tiny masked GEMMs as one batched launch with degenerate block
    shapes (K = R·S), and the producer's fused-encode bitmap drives all
    three stages exactly as for the dense convs."""
    return _conv_engine(x_pre, w, stride, padding, policy, True,
                        x_pre.shape[-1])


def depthwise_conv(x: jnp.ndarray, w: jnp.ndarray, stride: int,
                   padding: str, policy: SparsityPolicy):
    """Depthwise conv over signed x (no fused ReLU): groups == C."""
    return _conv_engine(x, w, stride, padding, policy, False, x.shape[-1])


# Back-compat aliases used by tests/benchmarks that reach for the raw pair.
_relu_conv_fwd = functools.partial(_conv_engine_fwd, fused_relu=True,
                                   groups=1)
_conv_fwd = functools.partial(_conv_engine_fwd, fused_relu=False, groups=1)
