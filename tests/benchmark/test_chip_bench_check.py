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
from chip_bench_tiny import tiny_cell  # noqa: E402

WORKLOADS = [w["name"] for w in spec.load_benchmark(ROOT)["workloads"]
             if w["chips"] == 1]


def _run(cell, build_step=program.build_step):
    return harness.run(cell, 2**31 + 5, 0.2, False, jax.devices("cpu"),
                       time.perf_counter(), build_step)


def _half_batch(config):
    """The program's step on the first half of the batch only."""
    step = program.build_step(config)
    return jax.jit(
        lambda p, x, y: step(p, x[:x.shape[0] // 2], y[:y.shape[0] // 2]))


def _unchanged_state(config):
    """The program's step returning the state it was given."""
    step = program.build_step(config)

    def broken(p, x, y):
        _, loss = step(p, x, y)
        return p, loss
    return jax.jit(broken)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_program_is_correct(workload):
    out = _run(tiny_cell(workload))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(check.NUMBERS) <= set(out["checks"])


@pytest.mark.parametrize("fault", [_half_batch, _unchanged_state],
                         ids=["half_batch", "unchanged_state"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_step_is_not_correct(workload, fault):
    out = _run(tiny_cell(workload), fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bfloat16_control_is_not_correct(workload):
    cell = tiny_cell(workload)
    ref = cell.reference()
    key = jax.random.key(3)
    params0 = ref.init_params(cell.config, key)
    f = harness.imagegen.batch_fn(
        cell.traffic, batch=cell.global_batch,
        image_size=cell.config["image_size"],
        channels=cell.config["channels"],
        num_classes=cell.config["num_classes"])
    batches = [f(jax.random.fold_in(key, i)) for i in range(check.STEPS)]
    want = harness.run_reference(cell, params0, batches)
    got = harness.run_reference(cell, params0, batches, jnp.bfloat16)
    numbers = check.compare(got, want)
    assert not check.verdict(numbers, cell.limits), numbers
