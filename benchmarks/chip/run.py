#!/usr/bin/env python3
"""On-chip training benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload vgg16.b16.letterbox \
        --seed 7 --seconds 45 --trace 0

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  The same numbers end standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    from chipbench import spec
    cell = spec.load_cell(args.workload)

    import jax
    if jax.default_backend() != "tpu":
        print(f"run.py: needs a TPU, JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run.py: the program is not at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", file=sys.stderr)

    from chipbench import harness
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
