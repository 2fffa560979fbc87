"""Fused ReLU forward + block-bitmap encode Pallas kernel.

The paper's Encoder unit (§4.1, Fig. 8a) produces non-zero offset indices of
a freshly computed feature map once per layer, amortized over O(M·k²) reuse.
The TPU analogue emits, in the same pass that applies the ReLU, a
*fine-granularity* block bitmap that the rest of the training step derives
every mask it needs from (FP input masks, BP output masks, WG transposed
masks) — so sparsity metadata is a free byproduct of the forward pass,
exactly as in the paper.

The bitmap granularity (gr, gc) is decoupled from the launch tile (lr, lc):
one kernel invocation covers an (lr, lc) slab of the activation and reduces
it to an (lr//gr, lc//gc) sub-bitmap with two 0/1 indicator matmuls
(``bits.any_nonzero_t``), so even per-row granularities (needed by
the conv path, where the bitmap must stay spatially addressable for im2col
derivation) launch with a coarse grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bits import any_nonzero_t, bits_tile_shape, untile_bits


def _relu_encode_kernel(z_ref, y_ref, bm_ref, *, gr: int, gc: int):
    y = jnp.maximum(z_ref[...], jnp.zeros((), dtype=z_ref.dtype))
    y_ref[...] = y
    # y >= 0 everywhere, so any-nonzero <=> the sub-block has a live
    # activation.  Stored transposed and padded (lane-dense); the wrapper
    # transposes back.
    bm_ref[0, 0] = any_nonzero_t(y, gr, gc)


def relu_encode_kernel(
    z: jnp.ndarray,
    *,
    bm: int,
    bn: int,
    lr: int = 0,
    lc: int = 0,
    interpret: bool = False,
):
    """Returns (relu(z), bitmap) with bitmap shape (M//bm, N//bn) int32.

    (bm, bn) is the BITMAP granularity; (lr, lc) the launch tile (defaults:
    whole array — callers size it; the ops wrapper picks slabs of a few
    hundred to a few thousand rows).
    """
    m, n = z.shape
    lr = lr or m
    lc = lc or n
    assert m % lr == 0 and n % lc == 0, (z.shape, lr, lc)
    assert lr % bm == 0 and lc % bn == 0, (lr, lc, bm, bn)
    ni, nj = m // lr, n // lc
    cp, rp = bits_tile_shape(lr, lc, bm, bn)
    fn = pl.pallas_call(
        functools.partial(_relu_encode_kernel, gr=bm, gc=bn),
        grid=(ni, nj),
        in_specs=[pl.BlockSpec((lr, lc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((lr, lc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, cp, rp), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), z.dtype),
            jax.ShapeDtypeStruct((ni, nj, cp, rp), jnp.int32),
        ],
        interpret=interpret,
    )
    y, bits = fn(z)
    return y, untile_bits(bits, lr // bm, lc // bn)
