"""Block-sparse GEMM Pallas TPU kernels — the compute core of the paper.

The paper skips MACs at element granularity using per-neuron offset lanes
(input sparsity) and the forward-pass ReLU bitmap (output sparsity).  The
TPU-native unit of skipping is an MXU block, so both sparsity types become
*block bitmaps*:

  out_mask (Mb, Nb):  1 ⇔ the forward ReLU mask has ≥1 nonzero in this
                      output tile → the tile must be computed.  0 ⇔ the
                      Hadamard with σ'(z) would zero the whole tile → the
                      producer GEMM never computes it (OUTPUT sparsity).
  a_mask   (Mb, Kb):  1 ⇔ the incoming-gradient tile has ≥1 nonzero
                      (INPUT sparsity; the paper's TC-sparsity offsets).
  b_mask   (Kb, Nb):  same for the second operand (used by the WG stage,
                      where both activations and gradients are sparse).

Two schedules are provided:

  * *predicated* (``grouped_masked_matmul_kernel``): full (G, Mb, Nb, Kb)
    grid, each step guards its MXU issue and its accumulator write with
    ``pl.when``.  This mirrors the paper's baseline sparse PE (lanes idle
    on skipped work → load imbalance across tiles).

  * *compacted* ("work redistribution",
    ``grouped_compact_masked_matmul_kernel``): the grid walks a scalar-
    prefetched queue of ACTIVE (g, i, j) block coordinates only, so work
    per sequential grid step is uniform by construction.  This is the TPU
    analogue of the paper's WDU (§4.6): the WDU rebalances remaining work
    at runtime; here the work-queue is compacted before launch, which
    achieves the same ideal occupancy bound the WDU approaches (its ~83%
    vs the queue's 100% of active blocks).

All kernels accumulate in a f32 VMEM scratch across the K grid dimension
and are exact: a skipped output tile is exactly the zero tile the dense
computation would have produced post-Hadamard.

Since the spec-driven redesign (docs/gemm_api.md), ``kernels.ops.
sparse_gemm`` launches ONLY the grouped kernels — a 2-D GEMM is the G=1
special case.  The 2-D kernels (``masked_matmul_kernel``,
``compact_masked_matmul_kernel``) are RETAINED as the pre-redesign
reference: tests/test_gemm_spec.py pins sparse_gemm(G=1) bit-exact against
them, the same role the argsort queue builder plays for the prefix-sum one.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bits import any_nonzero_t, bits_tile_shape, untile_bits


def _mxu_dot(a, b):
    """The kernels' MXU product, accumulated in f32.  Its precision follows
    ``jax.default_matmul_precision``: Mosaic's default (one bf16 pass, as
    XLA's default) where the setting is unset, "default" or "bfloat16", and
    full f32 for any other value, since Mosaic lowers only those two and a
    kernel must not compute below the precision asked for.  Pallas ignores
    that setting unless it is passed on, so a reference computed at HIGHEST
    would otherwise face default-precision kernels."""
    lowest = jax.config.jax_default_matmul_precision in (
        None, "default", "bfloat16")
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=None if lowest else jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# Predicated kernel (2-D; retained pre-redesign reference — see module doc)
# ---------------------------------------------------------------------------

def _mm_kernel(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, o_ref, acc_ref):
    """Grid = (Mb, Nb, Kb); K innermost so ``acc_ref`` accumulates per tile."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Output sparsity: the whole (i, j) tile is dead if the ReLU bitmap says
    # so.  Input sparsity: this K-step contributes nothing if either operand
    # tile is all-zero.
    active = (
        (out_m_ref[i, j] != 0)
        & (a_m_ref[i, k] != 0)
        & (b_m_ref[k, j] != 0)
    )

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_epilogue_kernel(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, mult_ref,
                        o_ref, acc_ref):
    """Predicated kernel + fused σ′-Hadamard epilogue: the final accumulator
    write multiplies by the (bm, bn) tile of ``mult`` — the backward pass's
    ``dx * σ'(z)`` never round-trips through HBM as a separate VPU pass."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    active = (
        (out_m_ref[i, j] != 0)
        & (a_m_ref[i, k] != 0)
        & (b_m_ref[k, j] != 0)
    )

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = (acc_ref[...] * mult_ref[...]).astype(o_ref.dtype)


def masked_matmul_kernel(
    a: jnp.ndarray,
    b: jnp.ndarray,
    out_mask: jnp.ndarray,
    a_mask: jnp.ndarray,
    b_mask: jnp.ndarray,
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=jnp.float32,
    epilogue_mult: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw predicated kernel launch.  Shapes must be block-aligned.

    ``epilogue_mult`` (M, N) f32, if given, is Hadamard-applied to each
    output tile inside the kernel at accumulator-writeback time.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (a.shape, b.shape, bm, bk, bn)
    ni, nj, nk = m // bm, n // bn, k // bk
    assert out_mask.shape == (ni, nj), (out_mask.shape, (ni, nj))
    assert a_mask.shape == (ni, nk), (a_mask.shape, (ni, nk))
    assert b_mask.shape == (nk, nj), (b_mask.shape, (nk, nj))

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
    ]
    operands = [a, b]
    kernel = _mm_kernel
    if epilogue_mult is not None:
        assert epilogue_mult.shape == (m, n), (epilogue_mult.shape, (m, n))
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)))
        operands.append(epilogue_mult.astype(jnp.float32))
        kernel = _mm_epilogue_kernel

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ni, nj, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )
    return fn(
        out_mask.astype(jnp.int32),
        a_mask.astype(jnp.int32),
        b_mask.astype(jnp.int32),
        *operands,
    )


# ---------------------------------------------------------------------------
# Composable epilogue stages — ONE application point per kernel family
# ---------------------------------------------------------------------------

def _apply_epilogue(acc, mult_tile, o_dtype, emit_gran):
    """The single epilogue application point, shared by both grouped kernel
    families.  Stages compose in canonical order:

      1. ``sigma_prime`` — Hadamard with the (already-gathered) multiplier
         tile (``mult_tile`` is None when the stage is off);
      2. ``bitmap_emit`` — reduce the POST-σ′ tile to its (er, ec)
         any-nonzero bitmap (``emit_gran`` is None when the stage is off),
         so the emitted bits describe exactly the values written back.

    ``acc`` may be (bm, bn) (predicated family) or (1, bm, bn) (compact
    family); the returned bits are the transposed, padded
    ``bits_tile_shape(bm, bn, er, ec)`` tile of ``any_nonzero_t``.
    """
    out = acc if mult_tile is None else acc * mult_tile
    bits = None
    if emit_gran is not None:
        bits = any_nonzero_t(out if out.ndim == 2 else out[0], *emit_gran)
    return out.astype(o_dtype), bits


def _epilogue_refs(refs, has_mult, emit_gran):
    """Decode the trailing ref list ``[mult?] o [bits?] acc`` of a variant
    kernel: optional multiplier first, output(s) in the middle, the f32
    accumulator scratch always last."""
    mult_ref = refs[0] if has_mult else None
    o_ref = refs[1] if has_mult else refs[0]
    bits_ref = refs[-2] if emit_gran is not None else None
    return mult_ref, o_ref, bits_ref, refs[-1]


# ---------------------------------------------------------------------------
# Grouped predicated kernel — one launch covers all G independent GEMMs of a
# grouped/depthwise conv (grid gains a leading group dimension; masks carry a
# leading G axis).  Semantics per group are identical to the 2-D kernel.
#
# The block masks are scalar-prefetched FLAT (1-D, row-major over their
# (G, ·, ·) shape): SMEM pads the minor dim of a multi-dim array to 128
# words, which at conv shapes (Mb in the thousands, Kb/Nb of a few) blew
# the 1 MiB SMEM budget.  The emitted bitmap is stored transposed and
# padded (``bits_tile_shape``) so its block satisfies the (8, 128) rule;
# the wrappers slice and transpose it back to (G, M//er, N//ec).
# ---------------------------------------------------------------------------

def _gmm_kernel(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, *refs,
                has_mult: bool = False,
                emit_gran: Optional[Tuple[int, int]] = None):
    """Grid = (G, Mb, Nb, Kb); K innermost so ``acc_ref`` accumulates.

    One body serves every epilogue combination — the trailing refs are
    ``[mult?] o [bits?] acc`` per ``_epilogue_refs`` and the writeback goes
    through ``_apply_epilogue`` (the only place stages are applied)."""
    mult_ref, o_ref, bits_ref, acc_ref = \
        _epilogue_refs(refs, has_mult, emit_gran)
    g = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    ni = pl.num_programs(1)
    nj = pl.num_programs(2)
    nk = pl.num_programs(3)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    active = (
        (out_m_ref[(g * ni + i) * nj + j] != 0)
        & (a_m_ref[(g * ni + i) * nk + k] != 0)
        & (b_m_ref[(g * nk + k) * nj + j] != 0)
    )

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[0], b_ref[0])

    @pl.when(k == nk - 1)
    def _write():
        out, bits = _apply_epilogue(
            acc_ref[...], None if mult_ref is None else mult_ref[0],
            o_ref.dtype, emit_gran)
        o_ref[0] = out
        if bits_ref is not None:
            bits_ref[0, 0, 0] = bits


def gmm_kernel_variant(has_mult: bool,
                       emit_gran: Optional[Tuple[int, int]] = None):
    """The predicated family's variant selector: binds the epilogue
    configuration onto ``_gmm_kernel`` (a named closure so the sanitizer's
    ``__module__``/``__name__`` resolution keeps working)."""
    if not has_mult and emit_gran is None:
        return _gmm_kernel

    def kernel(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, *refs):
        _gmm_kernel(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, *refs,
                    has_mult=has_mult, emit_gran=emit_gran)

    kernel.__name__ = f"_gmm_kernel[mult={int(has_mult)},emit={emit_gran}]"
    return kernel


def _flat_i32(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(-1).astype(jnp.int32)


def grouped_masked_matmul_kernel(
    a: jnp.ndarray,          # (G, M, K) block-aligned
    b: jnp.ndarray,          # (G, K, N)
    out_mask: jnp.ndarray,   # (G, Mb, Nb) int32
    a_mask: jnp.ndarray,     # (G, Mb, Kb)
    b_mask: jnp.ndarray,     # (G, Kb, Nb)
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=jnp.float32,
    epilogue_mult: Optional[jnp.ndarray] = None,   # (G, M, N) f32
    emit_gran: Optional[Tuple[int, int]] = None,
    interpret: bool = False,
):
    """Raw grouped predicated launch: G independent masked GEMMs, one grid.

    With ``emit_gran=(er, ec)`` the launch grows a second output — the
    packed (G, M//er, N//ec) int32 any-nonzero bitmap of the written
    values, emitted at accumulator writeback — and returns ``(out, bits)``.
    """
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (a.shape, bm, bk, bn)
    ni, nj, nk = m // bm, n // bn, k // bk
    assert out_mask.shape == (g, ni, nj), (out_mask.shape, (g, ni, nj))
    assert a_mask.shape == (g, ni, nk), (a_mask.shape, (g, ni, nk))
    assert b_mask.shape == (g, nk, nj), (b_mask.shape, (g, nk, nj))

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda gi, i, j, k, *_: (gi, i, k)),
        pl.BlockSpec((1, bk, bn), lambda gi, i, j, k, *_: (gi, k, j)),
    ]
    operands = [a, b]
    if epilogue_mult is not None:
        assert epilogue_mult.shape == (g, m, n), epilogue_mult.shape
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda gi, i, j, k, *_: (gi, i, j)))
        operands.append(epilogue_mult.astype(jnp.float32))
    kernel = gmm_kernel_variant(epilogue_mult is not None, emit_gran)

    out_specs = pl.BlockSpec((1, bm, bn), lambda gi, i, j, k, *_: (gi, i, j))
    out_shape = jax.ShapeDtypeStruct((g, m, n), out_dtype)
    if emit_gran is not None:
        er, ec = emit_gran
        assert bm % er == 0 and bn % ec == 0, (emit_gran, bm, bn)
        cp, rp = bits_tile_shape(bm, bn, er, ec)
        out_specs = [out_specs, pl.BlockSpec(
            (1, 1, 1, cp, rp), lambda gi, i, j, k, *_: (gi, i, j, 0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((g, ni, nj, cp, rp), jnp.int32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g, ni, nj, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )
    res = fn(_flat_i32(out_mask), _flat_i32(a_mask), _flat_i32(b_mask),
             *operands)
    if emit_gran is None:
        return res
    er, ec = emit_gran
    return res[0], untile_bits(res[1], bm // er, bn // ec)


# ---------------------------------------------------------------------------
# Grouped compacted kernel — ONE queue spans all groups: slots carry (g, i, j)
# triples in lexicographic order, so the work-redistribution schedule stays a
# single uniform stream even when every group contributes only a few tiles
# (the depthwise regime).  Masks and bits use the same flat / transposed
# layouts as the predicated family.
# ---------------------------------------------------------------------------

def _gmm_compact_kernel(
    gg_ref, ii_ref, jj_ref, n_act_ref, a_m_ref, b_m_ref, a_ref, b_ref,
    *refs, tiles: Tuple[int, int], has_mult: bool = False,
    emit_gran: Optional[Tuple[int, int]] = None
):
    """Grid = (S, Kb).  Step s processes active tile (gg[s], ii[s], jj[s]).

    ``tiles`` is the static (Mb, Nb) tile grid the flat masks are laid out
    on (the queue grid does not carry it).  One body serves every epilogue
    combination — the trailing refs are ``[mult?] o [bits?] acc`` per
    ``_epilogue_refs`` and the writeback goes through ``_apply_epilogue``.
    With emission on, each queue slot writes its own bits tile; dead slots
    write zeros (their accumulator never left zero), so the caller's
    scatter stays exact."""
    mult_ref, o_ref, bits_ref, acc_ref = \
        _epilogue_refs(refs, has_mult, emit_gran)
    ni, nj = tiles
    s = pl.program_id(0)
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = gg_ref[s]
    i = ii_ref[s]
    j = jj_ref[s]
    live = s < n_act_ref[0]
    active = (live & (a_m_ref[(g * ni + i) * nk + k] != 0)
              & (b_m_ref[(g * nk + k) * nj + j] != 0))

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[0], b_ref[0])

    @pl.when(k == nk - 1)
    def _write():
        out, bits = _apply_epilogue(
            acc_ref[...], None if mult_ref is None else mult_ref[0],
            o_ref.dtype, emit_gran)
        o_ref[...] = out
        if bits_ref is not None:
            bits_ref[0] = bits


def gmm_compact_kernel_variant(has_mult: bool,
                               emit_gran: Optional[Tuple[int, int]] = None,
                               *, tiles: Tuple[int, int]):
    """The compact family's variant selector (see ``gmm_kernel_variant``);
    ``tiles`` is the (Mb, Nb) grid the flat masks index."""

    def kernel(gg_ref, ii_ref, jj_ref, n_act_ref, a_m_ref, b_m_ref,
               a_ref, b_ref, *refs):
        _gmm_compact_kernel(gg_ref, ii_ref, jj_ref, n_act_ref, a_m_ref,
                            b_m_ref, a_ref, b_ref, *refs, tiles=tiles,
                            has_mult=has_mult, emit_gran=emit_gran)

    kernel.__name__ = \
        f"_gmm_compact_kernel[mult={int(has_mult)},emit={emit_gran}]"
    return kernel


def grouped_compact_masked_matmul_kernel(
    a: jnp.ndarray,           # (G, M, K)
    b: jnp.ndarray,           # (G, K, N)
    gg: jnp.ndarray,          # (S,) int32 — active tile group coords
    ii: jnp.ndarray,          # (S,) int32
    jj: jnp.ndarray,          # (S,) int32
    n_active: jnp.ndarray,    # (1,) int32
    a_mask: jnp.ndarray,      # (G, Mb, Kb)
    b_mask: jnp.ndarray,      # (G, Kb, Nb)
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=jnp.float32,
    epilogue_mult: Optional[jnp.ndarray] = None,
    emit_gran: Optional[Tuple[int, int]] = None,
    interpret: bool = False,
):
    """Returns the COMPACTED output (S, bm, bn); caller scatters to (G, M, N).

    With ``emit_gran=(er, ec)`` also returns the compacted
    (S, bm//er, bn//ec) int32 bits per queue slot — scattered back by the
    caller with the same steered coordinates as the output tiles.
    """
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2
    ni, nj, nk = m // bm, n // bn, k // bk
    (s_cap,) = ii.shape
    assert gg.shape == (s_cap,) and jj.shape == (s_cap,)

    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda s, k, gg, ii, jj, *_: (gg[s], ii[s], k)),
        pl.BlockSpec((1, bk, bn), lambda s, k, gg, ii, jj, *_: (gg[s], k, jj[s])),
    ]
    operands = [a, b]
    if epilogue_mult is not None:
        assert epilogue_mult.shape == (g, m, n), epilogue_mult.shape
        in_specs.append(pl.BlockSpec(
            (1, bm, bn), lambda s, k, gg, ii, jj, *_: (gg[s], ii[s], jj[s])))
        operands.append(epilogue_mult.astype(jnp.float32))
    kernel = gmm_compact_kernel_variant(epilogue_mult is not None, emit_gran,
                                        tiles=(ni, nj))

    out_specs = pl.BlockSpec((1, bm, bn), lambda s, k, *_: (s, 0, 0))
    out_shape = jax.ShapeDtypeStruct((s_cap, bm, bn), out_dtype)
    if emit_gran is not None:
        er, ec = emit_gran
        assert bm % er == 0 and bn % ec == 0, (emit_gran, bm, bn)
        cp, rp = bits_tile_shape(bm, bn, er, ec)
        out_specs = [out_specs, pl.BlockSpec(
            (1, cp, rp), lambda s, k, *_: (s, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (s_cap, cp, rp), jnp.int32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(s_cap, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((1, bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )
    res = fn(
        gg.astype(jnp.int32),
        ii.astype(jnp.int32),
        jj.astype(jnp.int32),
        n_active.astype(jnp.int32),
        _flat_i32(a_mask),
        _flat_i32(b_mask),
        *operands,
    )
    if emit_gran is None:
        return res
    er, ec = emit_gran
    return res[0], untile_bits(res[1][:, None, None], bm // er, bn // ec)


# ---------------------------------------------------------------------------
# Compacted (work-redistribution) kernel (2-D; retained pre-redesign
# reference — see module doc)
# ---------------------------------------------------------------------------

def _mm_compact_kernel(
    ii_ref, jj_ref, n_act_ref, a_m_ref, b_m_ref, a_ref, b_ref, o_ref, acc_ref
):
    """Grid = (S, Kb).  Step s processes active tile (ii[s], jj[s])."""
    s = pl.program_id(0)
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = ii_ref[s]
    j = jj_ref[s]
    live = s < n_act_ref[0]
    active = live & (a_m_ref[i, k] != 0) & (b_m_ref[k, j] != 0)

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _write():
        # Padding steps (s >= n_active) emit a zero tile; the wrapper
        # scatter-adds, so those land harmlessly on tile (0, 0).
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_compact_epilogue_kernel(
    ii_ref, jj_ref, n_act_ref, a_m_ref, b_m_ref, a_ref, b_ref, mult_ref,
    o_ref, acc_ref
):
    """Compacted schedule + fused σ′-Hadamard epilogue (mult tile gathered
    at the active coordinate (ii[s], jj[s]) via scalar prefetch)."""
    s = pl.program_id(0)
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = ii_ref[s]
    j = jj_ref[s]
    live = s < n_act_ref[0]
    active = live & (a_m_ref[i, k] != 0) & (b_m_ref[k, j] != 0)

    @pl.when(active)
    def _issue_mxu():
        acc_ref[...] += _mxu_dot(a_ref[...], b_ref[...])

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = (acc_ref[...] * mult_ref[...]).astype(o_ref.dtype)


def compact_masked_matmul_kernel(
    a: jnp.ndarray,
    b: jnp.ndarray,
    ii: jnp.ndarray,          # (S,) int32 — active tile row coords (0-padded)
    jj: jnp.ndarray,          # (S,) int32 — active tile col coords (0-padded)
    n_active: jnp.ndarray,    # (1,) int32 — number of live entries in ii/jj
    a_mask: jnp.ndarray,
    b_mask: jnp.ndarray,
    *,
    bm: int,
    bk: int,
    bn: int,
    out_dtype=jnp.float32,
    epilogue_mult: Optional[jnp.ndarray] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns the COMPACTED output (S, bm, bn); caller scatters to (M, N).

    The compacted layout is the explicit "work queue" of the paper's WDU:
    each sequential grid step carries exactly one active tile's worth of
    work, so there is no inter-tile idle time to redistribute.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    ni, nj, nk = m // bm, n // bn, k // bk
    (s_cap,) = ii.shape
    assert jj.shape == (s_cap,)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda s, k, ii, jj, *_: (ii[s], k)),
        pl.BlockSpec((bk, bn), lambda s, k, ii, jj, *_: (k, jj[s])),
    ]
    operands = [a, b]
    kernel = _mm_compact_kernel
    if epilogue_mult is not None:
        assert epilogue_mult.shape == (m, n), (epilogue_mult.shape, (m, n))
        in_specs.append(
            pl.BlockSpec((bm, bn), lambda s, k, ii, jj, *_: (ii[s], jj[s])))
        operands.append(epilogue_mult.astype(jnp.float32))
        kernel = _mm_compact_epilogue_kernel

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_cap, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, k, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_cap, bm, bn), out_dtype),
        interpret=interpret,
    )
    return fn(
        ii.astype(jnp.int32),
        jj.astype(jnp.int32),
        n_active.astype(jnp.int32),
        a_mask.astype(jnp.int32),
        b_mask.astype(jnp.int32),
        *operands,
    )
