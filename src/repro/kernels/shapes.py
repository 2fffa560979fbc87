"""Shared shape/padding/bitmap helpers for the masked-GEMM stack.

The old 2-D/grouped orchestrator split left near-identical private copies of
these scattered across ``kernels/ops.py`` (``_ceil_to``/``_pad_to``/
``_pad3``/``_pad_mask``/``_block_bitmap``), ``core/policy.py`` (a second
``_ceil_to``) and ``core/sparse_linear.py`` (the padded-scan oracle).  This
module is their single home; everything here is pure shape arithmetic with
zero policy or kernel knowledge, so any layer may import it.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp

from . import ref


def ceil_to(v: int, b: int) -> int:
    """Round ``v`` up to the next multiple of ``b``."""
    return -(-v // b) * b


def pad_to(x: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Zero-pad a 2-D array up to (m, n) on the trailing edges."""
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


def pad3(x: jnp.ndarray, d1: int, d2: int) -> jnp.ndarray:
    """Zero-pad a (G, ·, ·) array up to (G, d1, d2) on the trailing edges —
    the grouped form of ``pad_to`` (the leading group axis is never padded)."""
    p1, p2 = d1 - x.shape[1], d2 - x.shape[2]
    if p1 == 0 and p2 == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, p1), (0, p2)))


def ones_bitmap(nb0: int, nb1: int) -> jnp.ndarray:
    """All-live (nb0, nb1) tile bitmap — the ``mask=None`` (dense) meaning."""
    return jnp.ones((nb0, nb1), jnp.int32)


def pad_mask(mask: Optional[jnp.ndarray], nb0: int, nb1: int) -> jnp.ndarray:
    """A (≤nb0, ≤nb1) tile bitmap zero-padded to (nb0, nb1); ``None`` means
    dense ⇒ all-ones.  Padded tiles describe padded (all-zero) data, so the
    zero fill is the exact bitmap of that data."""
    if mask is None:
        return ones_bitmap(nb0, nb1)
    mask = mask.astype(jnp.int32)
    p0, p1 = nb0 - mask.shape[0], nb1 - mask.shape[1]
    if p0 or p1:
        mask = jnp.pad(mask, ((0, p0), (0, p1)))
    return mask


def pad_mask3(mask: Optional[jnp.ndarray], g: int, nb0: int,
              nb1: int) -> jnp.ndarray:
    """Grouped form of ``pad_mask``: (G, ≤nb0, ≤nb1) → (G, nb0, nb1)."""
    if mask is None:
        return jnp.ones((g, nb0, nb1), jnp.int32)
    return pad3(mask.astype(jnp.int32), nb0, nb1)


def block_bitmap(x: jnp.ndarray, b0: int, b1: int) -> jnp.ndarray:
    """Any-nonzero block bitmap of a 2-D array at tile (b0, b1), zero-padding
    ragged edges first (padding is dead data, so its bits are 0).  This is
    the one dense-scan primitive shared by the kernel wrappers and the
    threading tests' freshly-scanned oracle."""
    m, n = x.shape
    return ref.block_any_nonzero(pad_to(x, ceil_to(m, b0), ceil_to(n, b1)),
                                 b0, b1)


# f32 elements of lane-padded slab per row-slab kernel block (1 MiB).
SLAB_ELEMS = 1 << 18


def slab_rows(m: int, gr: int, cols: int) -> int:
    """Launch-slab height for a row-slab kernel over an (M, cols) array
    whose bitmap has row granularity ``gr``: a multiple of lcm(gr, 128)
    (so the slab's transposed bitmap is lane-dense) that divides M rounded
    up to that unit (so no padding beyond one unit), with at most about
    ``SLAB_ELEMS`` f32 elements of lane-padded slab per block."""
    unit = math.lcm(gr, 128)
    q = ceil_to(m, unit) // unit
    cap = max(1, SLAB_ELEMS // ceil_to(cols, 128) // unit)
    return unit * max(d for d in range(1, min(q, cap) + 1) if q % d == 0)


def grid_shape(dims: Tuple[int, ...], block: Tuple[int, ...]) -> Tuple[int, ...]:
    """Per-axis tile counts: ceil(dim / edge) for each (dim, edge) pair."""
    assert len(dims) == len(block), (dims, block)
    return tuple(ceil_to(d, e) // e for d, e in zip(dims, block))
