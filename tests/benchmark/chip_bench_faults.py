"""The program's training step with its timed path broken underneath, and
the bfloat16 control in its place, for the tests that see them come out
not correct.  Each broken step takes the config and, on several chips, the
mesh, as ``program.build_step``."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from chipbench import check, harness, imagegen, program


def bfloat16_control(cell) -> dict:
    """The five numbers of the reference computed in bfloat16 against the
    reference, over three of the cell's batches from seed 3."""
    key = imagegen.seed_key(3)
    params0 = cell.reference().init_params(cell.config, key)
    f = imagegen.batch_fn(
        cell.traffic, batch=cell.global_batch,
        image_size=cell.config["image_size"],
        channels=cell.config["channels"],
        num_classes=cell.config["num_classes"])
    batches = [f(jax.random.fold_in(key, i)) for i in range(check.STEPS)]
    want = harness.run_reference(cell, params0, batches)
    got = harness.run_reference(cell, params0, batches, jnp.bfloat16)
    return check.compare(got, want)


def half_batch(config, mesh=None):
    """The program's step on the first half of the batch only."""
    step = program.build_step(config, mesh)
    return jax.jit(
        lambda p, x, y: step(p, x[:x.shape[0] // 2], y[:y.shape[0] // 2]))


def unchanged_state(config, mesh=None):
    """The program's step returning the state it was given."""
    step = program.build_step(config, mesh)

    def broken(p, x, y):
        _, loss = step(p, x, y)
        return p, loss
    return jax.jit(broken)


def no_allreduce(config, mesh):
    """The data-parallel step without its gradient all-reduce: each chip
    applies the gradient of its own images to its own copy of the
    weights."""
    model, policy = program.model_and_policy(config)

    def body(p, x, y):
        loss, grads = jax.value_and_grad(
            lambda q: model.loss(q, x, y, policy))(p)
        return program.sgd(p, grads, config["lr"]), loss
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))
