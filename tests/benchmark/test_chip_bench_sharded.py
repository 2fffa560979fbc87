"""The chip benchmark on several chips, driven on the CPU at a tiny size.

A cell on several chips runs the program's data-parallel step over a mesh
of them, against a reference computed in per-chip chunks.  JAX fixes its
device count when it starts, so each cell runs in a process of its own on
as many virtual CPU devices (``chip_bench_sharded.py``): the sound program
must come out correct; the step without its all-reduce, on half of the
batch, or returning its state unchanged, and the bfloat16 control, must
not.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import check, spec  # noqa: E402

SHARDED = [w for w in spec.load_benchmark(ROOT)["workloads"]
           if w["chips"] > 1]


@pytest.fixture(scope="module", params=SHARDED, ids=lambda w: w["name"])
def runs(request):
    chips = request.param["chips"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}").strip())
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_bench_sharded.py"),
         request.param["name"]],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_placement(runs):
    """Weights replicated, every batch split on its batch axis over the
    ``("data",)`` mesh of the cell's chips."""
    assert runs["mesh_axes"] == ["data"]
    assert runs["weights_replicated"] is True
    assert runs["batch_shard"][0] * runs["chips"] == runs["global_batch"]
    assert runs["sound"]["device"]["count"] == runs["chips"]


def test_sharded_sound_program_is_correct(runs):
    got = runs["sound"]
    assert got["correct"] is True, got["checks"]
    assert got["attempted"] >= 1
    assert set(check.NUMBERS) <= set(got["checks"])


@pytest.mark.parametrize("fault", ["no_allreduce", "half_batch",
                                   "unchanged_state"])
def test_sharded_broken_step_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["checks"]


def test_sharded_bfloat16_control_is_not_correct(runs):
    assert runs["control_bf16"]["correct"] is False, runs["control_bf16"]
