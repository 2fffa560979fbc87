#!/usr/bin/env python3
"""Records a chip trace of one cell for the trace reduction's test data.

    python3 benchmarks/chip/record_trace.py --workload vgg16.b16.letterbox \
        --seed 5 --steps 2 --out trace_vgg16

Runs the cell's set-up and a traced window of ``--steps`` steps, then
writes into ``--out``:

  layout.json   every plane of the trace, its lines, their event counts
                and a few events with their stats: what a reader of the
                trace needs to know about its layout;
  events.json   the window's events as ``tracing.load_events`` gives
                them, trimmed to the first traced step, with the compiled
                module's lines for the instructions those events name:
                the input of ``tracing.reduce``, small enough to commit
                under ``benchmarks/chip/testdata/``;
  hlo.txt       the whole compiled module.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def layout(path: str, samples: int = 4) -> list:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"name": line.name, "events": len(events),
                          "sample": [{"name": e.name,
                                      "dur_ns": e.duration_ns,
                                      "stats": {k: str(v) for k, v in
                                                dict(e.stats).items()}}
                                     for e in events[:samples]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def trim(events: list, hlo_text: str) -> dict:
    """Events of the first traced step and the window's span, with the
    compiled module's lines of the instructions they name."""
    from chipbench import tracing
    steps = sorted((e for e in events if e.get("host") == tracing.HOST_STEP),
                   key=lambda e: e["start_ns"])
    window = [e for e in events if e.get("host") == tracing.HOST_WINDOW]
    t0 = steps[0]["start_ns"]
    t1 = steps[1]["start_ns"] if len(steps) > 1 else window[0]["start_ns"] \
        + window[0]["dur_ns"]
    ops = tracing.hlo_ops(hlo_text)
    line = tracing.ops_line(events, ops)
    keep = [e for e in events if "host" in e and t0 <= e["start_ns"] < t1]
    keep += [e for e in events if e.get("line") == line
             and t0 <= e["start_ns"] < t1]
    keep.append(dict(window[0], start_ns=t0, dur_ns=t1 - t0))
    names = sorted({e["name"] for e in keep if "dev" in e} & set(ops))
    return {"events": keep,
            "hlo": "\n".join(hlo_line(n, *ops[n]) for n in names)}


def hlo_line(name: str, scope: str, pallas: bool) -> str:
    """One instruction of the compiled module cut to what
    ``tracing.hlo_ops`` reads: its name, its scope, whether it is a Pallas
    kernel."""
    target = ' custom_call_target="tpu_custom_call",' if pallas else ""
    return f'  %{name} = op(),{target} metadata={{op_name="{scope}"}}'


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import glob

    import jax
    if jax.default_backend() != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    from chipbench import harness, spec, tracing
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    cell = spec.load_cell(args.workload)
    s = harness.Setup(cell, args.seed, jax.devices()[:cell.chips])
    os.makedirs(args.out, exist_ok=True)
    raw = os.path.join(os.path.abspath(args.out), "raw")
    jax.profiler.start_trace(raw)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(tracing.HOST_WINDOW):
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation(tracing.HOST_STEP):
                s.step()
    window_s = time.perf_counter() - t
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(raw, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with open(os.path.join(args.out, "layout.json"), "w") as f:
        json.dump(layout(path), f, indent=1)
    events = tracing.load_events(path)
    hlo_text = s.compiled.as_text()
    with open(os.path.join(args.out, "events.json"), "w") as f:
        json.dump(trim(events, hlo_text), f)
    with open(os.path.join(args.out, "hlo.txt"), "w") as f:
        f.write(hlo_text)
    reduced = tracing.reduce(events, hlo_text)
    print(json.dumps({"window_s": window_s, "lines": reduced.lines,
                      "ops_line": reduced.line,
                      "busy_s": reduced.busy_s(),
                      "breakdown": reduced.breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
