"""Drives a run of a several-chip cell, shrunk as ``chip_bench_tiny`` does,
on virtual CPU devices: the sound program, the program with each fault
such a cell can have, and the bfloat16 control.  Prints one JSON object.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/benchmark/chip_bench_sharded.py vgg16.dp4.letterbox

The device count is fixed when JAX starts, so ``test_chip_bench_sharded``
runs this in a process of its own.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "benchmarks", "chip"),
                os.path.join(ROOT, "src"), HERE]

import jax  # noqa: E402

import chip_bench_faults as faults  # noqa: E402
from chip_bench_tiny import tiny_cell  # noqa: E402
from chipbench import check, harness, program  # noqa: E402

FAULTS = {"no_allreduce": faults.no_allreduce,
          "half_batch": faults.half_batch,
          "unchanged_state": faults.unchanged_state}


def main(workload: str) -> int:
    cell = tiny_cell(workload)
    devices = jax.devices("cpu")[:cell.chips]
    if len(devices) < cell.chips:
        print(f"{workload} needs {cell.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    mesh, weights, split = harness.placement(devices)
    shape = (cell.global_batch, cell.config["image_size"],
             cell.config["image_size"], cell.config["channels"])
    out = {"chips": cell.chips, "global_batch": cell.global_batch,
           "mesh_axes": list(mesh.axis_names),
           "weights_replicated": weights.is_fully_replicated,
           "batch_shard": list(split.shard_shape(shape))}
    for name, build in [("sound", program.build_step)] + sorted(
            FAULTS.items()):
        r = harness.run(cell, 2**31 + 5, 0.2, False, devices,
                        time.perf_counter(), build)
        out[name] = {"correct": r["correct"], "checks": r["checks"],
                     "attempted": r["attempted"], "device": r["device"]}
    numbers = faults.bfloat16_control(cell)
    out["control_bf16"] = {"numbers": numbers,
                           "correct": check.verdict(numbers, cell.limits)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
