#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

    python3 benchmarks/chip/calibrate.py --workload vgg16.b16.letterbox \
        --seeds 101-112 --control-seeds 101-103 --out cal.json

For every seed: the program's first three steps through the cell's
compiled step (compiled once), the plain reference after them at the
config's precision, and the five numbers of ``chipbench.check`` (the lower
readings); the same numbers against the reference at HIGHEST precision are
printed beside them for the record.  For the control seeds also: the
reference computed in bfloat16 in the program's place (the control), and
the reference with half of every batch left out, the mean taken over the
rest (a planted fault); on several chips also the reference over the
first chip's images alone, which is what that chip applies when the
gradient is not all-reduced (a planted fault).  A step that returns its
state unchanged reads 1 on both leaf numbers by construction and needs no
run.  Prints one JSON line per seed and a summary with each number's lower
reading (largest over the seeds), upper readings (smallest over the
control seeds) and the program's trace-time counters of its collectives.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def seed_list(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def first_grad(cell, params0, batch, dtype=None):
    """The reference's gradient on one batch, over the chunks the chips
    see, as host arrays."""
    import functools

    import jax
    from chipbench import check
    ref = cell.reference()
    kw = {} if dtype is None else {"dtype": dtype}
    _, g = check.chunked_value_and_grad(
        functools.partial(ref.loss, config=cell.config, **kw),
        cell.config["batch_per_chip"])(params0, *batch)
    return jax.device_get(g)


def grad_diff(got, want) -> float:
    """Worst leaf's ||got - want|| over the larger of ||want|| of that leaf
    and of the median leaf: a diagnostic beside ``grad_norm``."""
    import jax
    import numpy as np
    d = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.linalg.norm(np.asarray(a, np.float64) - b)),
        got, want))
    n = jax.tree.leaves(jax.tree.map(
        lambda b: float(np.linalg.norm(np.asarray(b, np.float64))), want))
    med = float(np.median(n))
    return max(x / max(y, med) for x, y in zip(d, n))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seed_list)
    ap.add_argument("--control-seeds", default="", type=lambda t: seed_list(t)
                    if t else [])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import check, harness, program, spec
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    cell = spec.load_cell(args.workload)
    devices = jax.devices()[:cell.chips]

    compiled, rows = None, []
    for seed in args.seeds:
        t = time.perf_counter()
        s = harness.Setup(cell, seed, devices, compiled=compiled)
        compiled = s.compiled
        got = s.program_readings()
        g_prog = jax.tree.map(lambda a, b: (a - b) / cell.config["lr"],
                              *jax.device_get((s.params0, s.p1)))
        params0, first = s.reference_inputs()
        s.free()
        ref = harness.run_reference(cell, params0, first)
        highest = harness.run_reference(cell, params0, first,
                                        precision="highest")
        g_ref = first_grad(cell, params0, first[0])
        row = {"seed": seed, "program": check.compare(got, ref),
               "grad_diff": {"program": grad_diff(g_prog, g_ref)},
               "program_vs_highest": check.compare(got, highest),
               "losses": {"program": got["losses"],
                          "reference": ref["losses"],
                          "highest": highest["losses"]}}
        if seed in args.control_seeds:
            ctrl = harness.run_reference(cell, params0, first, jnp.bfloat16)
            row["control_bf16"] = check.compare(ctrl, ref)
            row["grad_diff"]["control_bf16"] = grad_diff(
                first_grad(cell, params0, first[0], jnp.bfloat16), g_ref)
            half = [(x[:x.shape[0] // 2], y[:y.shape[0] // 2])
                    for x, y in first]
            row["fault_half_batch"] = check.compare(
                harness.run_reference(cell, params0, half), ref)
            if cell.chips > 1:
                per_chip = cell.config["batch_per_chip"]
                own = [(x[:per_chip], y[:per_chip]) for x, y in first]
                row["fault_no_allreduce"] = check.compare(
                    harness.run_reference(cell, params0, own), ref)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)

    summary = {"workload": args.workload, "lower": {}, "upper": {},
               "collectives": program.collective_counts()}
    for k in check.NUMBERS:
        summary["lower"][k] = max(r["program"][k] for r in rows)
        for kind in ("control_bf16", "fault_half_batch",
                     "fault_no_allreduce"):
            vals = [r[kind][k] for r in rows if kind in r]
            if vals:
                summary["upper"].setdefault(k, {})[kind] = min(vals)
        summary["upper"].setdefault(k, {})["fault_unchanged_state"] = (
            1.0 if k in ("grad_norm", "update_norm") else None)
    print(json.dumps(summary), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
