"""The chip benchmark's correctness check, driven on the CPU at a tiny size.

Each test skips the harness's look for a chip and drives the rest of a run
of a cell, shrunk to width 1/16, 32x32 images and a batch of 2: a sound
program must come out correct, and the program with its timed path broken
underneath, or the bfloat16 control in its place, must not.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import check, harness, program, spec  # noqa: E402
import chip_bench_faults as faults  # noqa: E402
from chip_bench_tiny import tiny_cell  # noqa: E402

WORKLOADS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]
             if w["chips"] == 1]


def _run(cell, build_step=program.build_step):
    return harness.run(cell, 2**31 + 5, 0.2, False, jax.devices("cpu"),
                       time.perf_counter(), build_step)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_program_is_correct(workload):
    out = _run(tiny_cell(workload))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(check.NUMBERS) <= set(out["checks"])


@pytest.mark.parametrize("fault", [faults.half_batch, faults.unchanged_state],
                         ids=["half_batch", "unchanged_state"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_step_is_not_correct(workload, fault):
    out = _run(tiny_cell(workload), fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bfloat16_control_is_not_correct(workload):
    cell = tiny_cell(workload)
    numbers = faults.bfloat16_control(cell)
    assert not check.verdict(numbers, cell.limits), numbers


@pytest.mark.parametrize("workload", WORKLOADS)
def test_one_chip_step_lowers_as_before(workload):
    """On one chip the step is what it was before the benchmark took
    several: ``jax.value_and_grad`` of the model's loss and SGD, in one
    ``jax.jit``, with no mesh and no collective."""
    cell = tiny_cell(workload)
    cfg = cell.config
    model, policy = program.model_and_policy(cfg)

    def step(params, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, images, labels, policy))(params)
        return program.sgd(params, grads, cfg["lr"]), loss

    params = jax.eval_shape(lambda k: cell.reference().init_params(cfg, k),
                            jax.random.key(0))
    n, s = cell.global_batch, cfg["image_size"]
    args = (params,
            jax.ShapeDtypeStruct((n, s, s, cfg["channels"]), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32))
    got = program.build_step(cfg).lower(*args).as_text()
    assert got == jax.jit(step).lower(*args).as_text()
    assert "all_reduce" not in got and "all-reduce" not in got
