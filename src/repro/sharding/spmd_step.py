"""``shard_map`` training step — the sparse engine under explicit SPMD.

The jit partitioner would happily shard the training step on its own, but
it cannot know the sparsity contracts: which psums may be compressed by
which bitmap, and that per-shard masks must be SLICES of the single
forward bitmap rather than per-shard rescans.  ``shard_map`` makes both
explicit:

  * the batch is sharded on its leading dim over the data-parallel axes;
    each shard's forward pass runs the SAME fused relu_encode on its rows,
    so the shard's ``SparseTensor`` bitmap IS the row-slice of the global
    bitmap (bitmaps tile rows at granularity ``gran[0]`` and shards split
    on row boundaries — `partition.bitmap_pspec` enforces the same
    alignment for explicitly sharded carriers).  The body is traced ONCE
    for all shards, so ``bitmap_op_audit`` still sees exactly one encode
    per activation per step across the whole mesh, and zero rescans;
  * every GEMM inside the body sees shard-LOCAL dims and resolves its own
    ``GemmSpec`` through the one ``SparsityPolicy.gemm_spec``/autotune
    path — per-shard dataflow selection (SparseTrain's point) falls out of
    the existing machinery;
  * the gradient all-reduce goes through
    ``sharding.collectives.psum_grads``: WG bitmaps registered by the
    backward pass compress the wire traffic, everything else takes the
    tagged dense psum.

``check_vma=False`` throughout: the bodies route through Pallas kernels
(custom_vjp + pallas_call), for which shard_map's varying-manual-axes
checker has no rules.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import collectives, partition

# Compressed-collective capacity as a fraction of the block count; above
# this union live fraction the all-reduce falls back to dense psum
# (docs/sharding.md#cutoff).
DEFAULT_CUTOFF = 0.5


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    axes = partition.dp_axis_names(mesh)
    if not axes:
        raise ValueError(
            f"mesh {mesh.axis_names} has no data-parallel axis "
            "('data'/'pod') to shard the batch over")
    return axes


def make_spmd_grad_fn(loss_fn: Callable[[Any, Any], Any], mesh: Mesh, *,
                      cutoff: float = DEFAULT_CUTOFF,
                      block: Optional[Tuple[int, int]] = None):
    """jit(shard_map) of ``loss_fn(params, batch) -> scalar mean loss``.

    Returns ``f(params, batch) -> (loss, grads)`` where ``batch`` is
    globally batched on its leading dim and the outputs are the GLOBAL
    mean loss and mean gradients — numerically the single-device
    ``value_and_grad`` of the same loss over the full batch (to psum
    accumulation-order tolerance; asserted in
    tests/test_sparse_collectives.py)."""
    axes = data_axes(mesh)
    inv = 1.0 / partition.axis_size(mesh, axes)

    def body(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = collectives.psum_grads(grads, axis_name=axes, cutoff=cutoff,
                                       block=block)
        loss = collectives.psum_scalar(loss, axes)
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(tuple(axes))),
        out_specs=(P(), P()), check_vma=False))


def make_spmd_train_step(cfg, opt_cfg, mesh: Mesh, *,
                         cutoff: float = DEFAULT_CUTOFF,
                         block: Optional[Tuple[int, int]] = None):
    """The LM training step of ``launch.steps.make_train_step``, as an
    explicit shard_map: (params, opt_state, batch) -> same triple, with
    params/opt replicated, the batch data-sharded, and the gradient
    all-reduce bitmap-compressed.  Gradient-accumulation microbatching is
    the jit path's feature; here the mesh IS the batch split
    (train_loop asserts microbatches == 1 in spmd mode)."""
    from repro.models.transformer import lm_loss
    from repro.optim.optimizer import adamw_update
    axes = data_axes(mesh)
    inv = 1.0 / partition.axis_size(mesh, axes)

    def body(params, opt_state, batch):
        def loss_fn(p):
            loss = lm_loss(p, batch, cfg)
            if opt_cfg.loss_scale > 0:
                return loss * opt_cfg.loss_scale, loss
            return loss, loss

        (_, loss), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = collectives.psum_grads(grads, axis_name=axes, cutoff=cutoff,
                                       block=block)
        grads = jax.tree.map(lambda g: g * inv, grads)
        loss = collectives.psum_scalar(loss, axes) * inv
        params, opt_state, metrics = adamw_update(
            grads, opt_state, params, opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    step = jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(), P(tuple(axes))),
                         out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1))
