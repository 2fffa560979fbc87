"""The stored layout of the any-nonzero bitmaps that kernels write.

Every kernel that writes a bitmap (``relu_encode``, ``bitmap_scan`` and the
GEMMs' ``bitmap_emit`` epilogue) stores each launch tile's bitmap
TRANSPOSED and zero-padded to the TPU's (8, 128) tiling, so the store is
lane-dense and its block passes Mosaic's tiling rule.  The wrappers cut
the padding off and transpose back outside the kernel with ``untile_bits``.
This module is the one place that layout is defined.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .shapes import ceil_to


def bits_tile_shape(rows: int, cols: int, gr: int,
                    gc: int) -> Tuple[int, int]:
    """Stored shape of the transposed bitmap of a (rows, cols) tile at
    granularity (gr, gc): (cols//gc, rows//gr) padded to (8, 128)."""
    return ceil_to(cols // gc, 8), ceil_to(rows // gr, 128)


def any_nonzero_t(v, gr: int, gc: int):
    """Transposed any-nonzero bitmap of a 2-D tile, in 2-D ops Mosaic
    lowers: ``bits[c, q] = any(v[q·gr:(q+1)·gr, c·gc:(c+1)·gc] != 0)``,
    shaped ``bits_tile_shape`` with zero padding.

    The reductions are 0/1 indicator matmuls (column groups, then row
    groups) rather than a 4-D reshape-max.  Operands are 0/1 and the counts
    are small integers, so the products are exact at any MXU precision."""
    rows, cols = v.shape
    cp, rp = bits_tile_shape(rows, cols, gr, gc)
    nz = (v != 0).astype(jnp.float32)
    c = jax.lax.broadcasted_iota(jnp.int32, (cp, cols), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (cp, cols), 1)
    col_sel = ((k >= c * gc) & (k < c * gc + gc)).astype(jnp.float32)
    cnt = jax.lax.dot_general(col_sel, nz, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if gr != 1 or rows != rp:
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, rp), 0)
        q = jax.lax.broadcasted_iota(jnp.int32, (rows, rp), 1)
        row_sel = ((r >= q * gr) & (r < q * gr + gr)).astype(jnp.float32)
        cnt = jnp.dot(cnt, row_sel, preferred_element_type=jnp.float32)
    return (cnt > 0).astype(jnp.int32)


def untile_bits(bits: jnp.ndarray, fr: int, fc: int) -> jnp.ndarray:
    """Stored tiles (..., A, B, cp, rp) → the (..., A·fr, B·fc) bitmap, where
    each tile's bitmap is (fr, fc) before transposing and padding."""
    *lead, a, b, _, _ = bits.shape
    t = bits[..., :fc, :fr]                      # (..., A, B, fc, fr)
    n = t.ndim
    t = t.transpose(*range(n - 4), n - 4, n - 1, n - 3, n - 2)
    return t.reshape(*lead, a * fr, b * fc)      # via (..., A, fr, B, fc)
