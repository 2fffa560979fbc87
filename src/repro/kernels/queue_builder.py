"""Work-queue construction: stream compaction by prefix sum.

The compacted schedule (masked_matmul.grouped_compact_masked_matmul_kernel)
consumes an explicit queue of active output-tile coordinates
``(ii, jj, n_active)``.  The seed built that queue with ``jnp.argsort``
over the flattened (Mb, Nb) tile bitmap — an O(T log T) sort sitting on
the critical path of every backward step, growing with model size.  The
WDU principle (paper §4.6, and the SparseTrain/TensorDash lesson) is that
scheduling metadata must be a near-free byproduct of the dataflow, so the
default builder replaces the sort with an exclusive-prefix-sum *stream
compaction*: O(T) work, one pass.

Algorithm (classic stream compaction):

  1. flatten the bitmap row-major (the WDU's "lexicographically smallest
     state tuple first" order is exactly row-major (i, j));
  2. exclusive prefix sum of the live flags gives each live element its
     queue slot;
  3. each live element stores its (i, j) = (t // Nb, t % Nb) at its slot.
     Dead elements — and live elements past ``capacity`` (overflow) — are
     steered to a dump slot one past the queue, so the scatter is
     unconditional and overflow never corrupts slots [0, capacity).

The bitmap holds tile counts, not elements, so the compaction is a few
small XLA ops (``cumsum`` + one scatter) rather than a Pallas kernel:
Mosaic has no lowering for an in-kernel ``cumsum``.

The emitted order is *identical* to the retained argsort reference (both
are row-major-stable); ``core.workredist.static_queue_order`` is the
executable statement of that contract and the property suite
(tests/test_queue_builder.py) pins all three against each other.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from . import stats


def _prefix_sum_queue(flat: jnp.ndarray, capacity: int, nb: int):
    """Exclusive-prefix-sum compaction of the flat {0,1} tile vector."""
    live = flat != 0
    flags = live.astype(jnp.int32)
    slot = jnp.where(live, jnp.cumsum(flags) - flags, capacity)
    slot = jnp.minimum(slot, capacity)          # overflow -> dump slot
    t = jnp.arange(flat.shape[0], dtype=jnp.int32)
    # Dead queue slots must hold VALID coordinates: the consumer gathers
    # operand tiles at (ii[s], jj[s]) even for s >= n_active — zeros.
    ii = jnp.zeros((capacity + 1,), jnp.int32).at[slot].set(t // nb)
    jj = jnp.zeros((capacity + 1,), jnp.int32).at[slot].set(t % nb)
    return ii[:capacity], jj[:capacity], flags.sum().reshape(1)


def _argsort_queue(flat: jnp.ndarray, capacity: int, nb: int):
    """The retained O(T log T) reference: stable descending argsort of the
    {0,1} vector (active indices first, row-major within each class)."""
    order = jnp.argsort(-flat, stable=True)[:capacity]
    if order.shape[0] < capacity:           # capacity may exceed T
        order = jnp.pad(order, (0, capacity - order.shape[0]))
    ii = (order // nb).astype(jnp.int32)
    jj = (order % nb).astype(jnp.int32)
    # Dead slots must carry valid (in-range) coords for the consumer's
    # gathers; zero them like the prefix-sum builder.
    live = jnp.arange(capacity) < flat.sum()
    ii = jnp.where(live, ii, 0)
    jj = jnp.where(live, jj, 0)
    return ii, jj, flat.sum().reshape(1)


_BUILDERS = {"prefix_sum": _prefix_sum_queue, "argsort": _argsort_queue}


def build_queue(
    bitmap: jnp.ndarray,
    *,
    capacity: int,
    builder: str = "prefix_sum",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Active-tile queue ``(ii, jj, n_live)`` from a (Mb, Nb) tile bitmap.

    Queue order is the WDU's "lexicographically smallest state tuple first"
    — row-major (i, j); ``core.workredist.static_queue_order`` is the
    reference.  ``ii``/``jj`` are (capacity,) int32, zero-padded past the
    live count; ``n_live`` (1,) is the TRUE set-bit count (it may exceed
    ``capacity`` — callers use that to trigger the overflow fallback).

    builder="prefix_sum" (default): the O(T) stream compaction above — no
    sort on the critical path.  builder="argsort": the seed's O(T log T)
    sort, kept as the reference.  Each construction is counted by
    ``stats`` as ``queue:<builder>``.
    """
    if builder not in _BUILDERS:
        raise ValueError(f"unknown queue builder: {builder!r}")
    _, nb = bitmap.shape
    stats.record(f"queue:{builder}")
    with stats.lifecycle_scope("queue", builder):
        flat = bitmap.reshape(-1).astype(jnp.int32)
        return _BUILDERS[builder](flat, capacity, nb)
