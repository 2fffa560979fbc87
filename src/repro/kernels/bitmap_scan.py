"""Pallas block-any-nonzero bitmap scan — the OFF-hot-path encoder.

Every training-step tensor now gets its bitmap from its PRODUCER:
``kernels.relu_encode`` makes the activation bitmap a free byproduct of
the forward ReLU, and the ``bitmap_emit`` GEMM epilogue stage
(``kernels.masked_matmul``, staged via ``GemmSpec.epilogue``) thresholds
each dy accumulator tile at writeback — so the ROADMAP "TPU-native
scan_bitmap" item's endgame landed and ``scan_pallas:*`` is identically
zero on the training hot path.  This standalone kernel survives for the
two jobs with no producing op to fuse into: the OPT-IN entry scan of raw
signed model inputs (``SparsityPolicy.scan_signed_inputs``) and the
numerical reference that emit-epilogue tests compare against.

Same granularity/launch-slab decoupling and the same indicator-matmul
reduction (``bits.any_nonzero_t``) as relu_encode, so the per-row
granularities the conv path needs stay cheap to launch.  Signed data ⇒ the
liveness predicate is ``x != 0``, not ``x > 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bits import any_nonzero_t, bits_tile_shape, untile_bits


def _bitmap_scan_kernel(x_ref, bm_ref, *, gr: int, gc: int):
    bm_ref[0, 0] = any_nonzero_t(x_ref[...], gr, gc)


def bitmap_scan_kernel(
    x: jnp.ndarray,
    *,
    bm: int,
    bn: int,
    lr: int = 0,
    lc: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns the (M//bm, N//bn) int32 any-nonzero bitmap of signed ``x``.

    (bm, bn) is the BITMAP granularity; (lr, lc) the launch tile (defaults:
    whole array — the ops wrapper sizes the slabs as for ``relu_encode``).
    """
    m, n = x.shape
    lr = lr or m
    lc = lc or n
    assert m % lr == 0 and n % lc == 0, (x.shape, lr, lc)
    assert lr % bm == 0 and lc % bn == 0, (lr, lc, bm, bn)
    ni, nj = m // lr, n // lc
    cp, rp = bits_tile_shape(lr, lc, bm, bn)
    fn = pl.pallas_call(
        functools.partial(_bitmap_scan_kernel, gr=bm, gc=bn),
        grid=(ni, nj),
        in_specs=[pl.BlockSpec((lr, lc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1, cp, rp), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ni, nj, cp, rp), jnp.int32),
        interpret=interpret,
    )
    return untile_bits(fn(x), lr // bm, lc // bn)
