"""One run of one cell: set-up, the measured window, the check, the result.

Set-up makes the weights and a pool of batches on the device from the
seed, compiles the cell's one step shape, and drives the compiled step
through its first three steps, each fenced with ``block_until_ready``,
which the check compares.  On one chip the weights and the pool live on
that chip; on several, the weights are replicated and every batch is split
over them on its leading axis (``placement``), and the step is the
program's data-parallel one.  The window then calls the same compiled step
on the pool's next batches, keeping ``AHEAD_S`` seconds of steps queued on
the device so that a host that stands still for a moment does not leave
the chip idle, for the given seconds (``--trace 0``) or for
``TRACE_STEPS`` steps under the profiler (``--trace 1``); when it is over
it sends nothing more and waits for every step it sent.  Once the window
has closed and the peak memory has been read, the program's state is
freed and the plain reference follows the first three steps.
"""
from __future__ import annotations

import collections
import functools
import math
import sys
import time
from typing import Callable, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from chipbench import check, flops, imagegen, layers, program, spec, tracing

TRACE_STEPS = 8
AHEAD_S = 4.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _memory_peak(devices: Sequence) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _compiled_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def placement(devices: Sequence):
    """(mesh, the weights' sharding, the pool batches' sharding) for a
    cell's devices: on one chip no mesh, and both on that chip; on several
    a ``("data",)`` mesh over them, the weights replicated and each batch
    split on its leading (batch) axis."""
    if len(devices) == 1:
        one = jax.sharding.SingleDeviceSharding(devices[0])
        return None, one, one
    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices)
    return (mesh, jax.sharding.NamedSharding(mesh, P()),
            jax.sharding.NamedSharding(mesh, P("data")))


class Setup:
    """The compiled step, its state after the first three steps, and the
    pool, built from the seed."""

    def __init__(self, cell: spec.Cell, seed: int, devices: Sequence,
                 build_step: Callable = program.build_step, compiled=None):
        self.cell, self.devices = cell, list(devices)
        cfg = cell.config
        ref = cell.reference()
        mesh, weights, split = placement(self.devices)
        key = imagegen.seed_key(seed)
        self.params0 = jax.jit(functools.partial(ref.init_params, cfg),
                               out_shardings=weights)(
                                   jax.random.fold_in(key, 0))
        one = imagegen.batch_fn(
            cell.traffic, batch=cell.global_batch,
            image_size=cfg["image_size"], channels=cfg["channels"],
            num_classes=cfg["num_classes"])
        n = int(cell.traffic["pool_batches"])
        pool_key = jax.random.fold_in(key, 1)
        self.batches = jax.jit(
            lambda k: [one(jax.random.fold_in(k, i)) for i in range(n)],
            out_shardings=split)(pool_key)
        jax.block_until_ready((self.params0, self.batches))

        t = time.perf_counter()
        self.compiled = compiled or build_step(cfg, mesh).lower(
            self.params0, *self.batches[0]).compile()
        self.compile_s = time.perf_counter() - t
        self.hbm_bytes = _compiled_bytes(self.compiled)

        self.params, self.next_batch = self.params0, 0
        self.losses = [float(self.step())]
        self.p1 = self.params
        self.losses += [float(self.step()) for _ in range(check.STEPS - 2)]
        t = time.perf_counter()
        self.losses.append(float(self.step()))
        self.step_s = time.perf_counter() - t
        self.p3 = self.params

    def dispatch(self):
        """Sends one step on the pool's next batch; returns its loss,
        still on the device."""
        b = self.batches[self.next_batch % len(self.batches)]
        self.next_batch += 1
        with jax.profiler.TraceAnnotation(tracing.HOST_DISPATCH):
            self.params, loss = self.compiled(self.params, *b)
        return loss

    def step(self):
        """One fenced step; returns its loss."""
        return wait(self.dispatch())

    def program_readings(self) -> dict:
        return check.states_readings(self.params0, self.p1, self.p3,
                                     self.losses, self.cell.config["lr"])

    def reference_inputs(self):
        """Host copies of the initial weights and the first three batches,
        for the reference once the program's state is gone."""
        host = jax.device_get((self.params0, self.batches[:check.STEPS]))
        return host[0], [tuple(b) for b in host[1]]

    def free(self) -> None:
        for name in ("compiled", "params", "params0", "p1", "p3", "batches"):
            setattr(self, name, None)


def wait(loss):
    with jax.profiler.TraceAnnotation(tracing.HOST_WAIT):
        return jax.block_until_ready(loss)


def window(s: Setup, over: Callable[[int, float], bool]) -> dict:
    """Dispatches steps, ``AHEAD_S`` seconds of them queued ahead of the
    one waited for, until ``over(steps sent, seconds)``; then waits for
    all that were sent and reads the clock.  A step's time is the gap
    between its completion and the one before; the losses are read once
    the window has closed."""
    ahead = max(1, math.ceil(AHEAD_S / s.step_s))
    queued, losses, ends = collections.deque(), [], []
    t0 = time.perf_counter()
    while not over(len(losses) + len(queued), time.perf_counter() - t0):
        queued.append(s.dispatch())
        if len(queued) > ahead:
            losses.append(wait(queued.popleft()))
            ends.append(time.perf_counter())
    while queued:
        losses.append(wait(queued.popleft()))
        ends.append(time.perf_counter())
    window_s = time.perf_counter() - t0
    return {"steps": len(losses), "window_s": window_s, "ahead": ahead,
            "times": np.diff([t0] + ends),
            "losses": [float(x) for x in losses]}


def timed_window(s: Setup, seconds: float) -> dict:
    return window(s, lambda steps, elapsed: elapsed >= seconds)


def traced_window(s: Setup, steps: int) -> dict:
    events, w = tracing.capture(
        lambda: window(s, lambda sent, elapsed: sent >= steps))
    return dict(w, events=events)


def zero_tiles(cell: spec.Cell, params, batch) -> Dict[str, float]:
    """Share of all-zero 128-pixel x 128-channel input tiles of each conv,
    on one batch, from the reference's forward pass."""
    ref = cell.reference()
    f = jax.jit(lambda p, x: {k: imagegen.zero_tile_fraction(v) for k, v in
                              ref.conv_inputs(p, x, cell.config).items()})
    rows = cell.config["batch_per_chip"]
    got = f(params, batch[0][:rows])
    return {n["name"]: float(got[n["name"]])
            for n in layers.iter_convs(cell.config["layers"])}


def run_reference(cell: spec.Cell, params0, batches, dtype=None,
                  precision=None) -> dict:
    """The reference's readings over ``batches`` from ``params0``: float32
    at the config's precision, or ``dtype`` / ``precision`` in its place;
    each step over chunks of ``batch_per_chip`` images, as the program's
    chips see them."""
    ref = cell.reference()
    kw = {} if dtype is None else {"dtype": dtype}
    loss_fn = functools.partial(ref.loss, config=cell.config,
                                precision=precision, **kw)
    with jax.default_device(jax.devices()[0]):
        return check.reference_readings(loss_fn, params0, batches,
                                        cell.config["lr"],
                                        cell.config["batch_per_chip"])


class MissingMetric(RuntimeError):
    """A per-layer metric of the cell whose reader found nothing to read."""


def per_layer(cell: spec.Cell, s: Setup, w: dict):
    """(per-layer metric values, the readers' context) of a traced window.
    Every per-layer metric that applies to the cell has to be read: one
    whose reader finds nothing means the trace does not look as the
    reduction expects, and fails the run."""
    kind = s.devices[0].device_kind
    ctx = tracing.Context(
        reduced=tracing.reduce(w["events"], s.compiled.as_text()),
        steps=w["steps"],
        window_s=w["window_s"], images=w["steps"] * cell.global_batch,
        chips=cell.chips, flops_per_image=flops.train_flops_per_image(
            cell.config),
        peak_flops=spec.peaks(kind)[cell.config["peak"]],
        hbm_bytes=s.hbm_bytes)
    out = {name: reader.read(ctx) for name, reader in cell.readers().items()}
    missing = sorted(k for k, v in out.items() if v is None)
    if missing:
        raise MissingMetric(
            f"{cell.name}: no reading of {', '.join(missing)} in the trace "
            f"(device lines {ctx.reduced.lines!r}, ops line "
            f"{ctx.reduced.line!r})")
    return out, ctx


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        devices: Sequence, t0: float,
        build_step: Callable = program.build_step) -> dict:
    """One run; returns the result line's object."""
    devices = list(devices)[:cell.chips]
    s = Setup(cell, seed, devices, build_step)
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s!r} compile_s={s.compile_s!r} "
        f"compiled_bytes={s.hbm_bytes} first_losses={s.losses!r}")

    if trace:
        w = traced_window(s, TRACE_STEPS)
    else:
        w = timed_window(s, seconds)
    window_losses = w["losses"]
    failed = sum(not math.isfinite(x) for x in window_losses)
    memory_peak = _memory_peak(devices)
    log(f"window: steps={w['steps']} window_s={w['window_s']!r} "
        f"queued_ahead={w['ahead']} "
        f"peak_bytes_in_use={memory_peak} (compiled footprint "
        f"{s.hbm_bytes} bytes)")

    metrics: Dict[str, dict] = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        values, ctx = per_layer(cell, s, w)
        device["busy_s"] = ctx.reduced.busy_s()
        device["window_s"] = w["window_s"]
        breakdown = ctx.reduced.breakdown()
    else:
        times = w["times"]
        values = {
            "images_per_s": w["steps"] * cell.global_batch / w["window_s"],
            "step_ms_p90": float(np.percentile(times, 90)) * 1e3,
            "setup_s": setup_s,
        }
        slow = np.argsort(times)[::-1][:3]
        log(f"step_ms: median={np.median(times) * 1e3!r} "
            f"min={times.min() * 1e3!r} slowest (step, ms)="
            f"{[(int(i), float(times[i]) * 1e3) for i in slow]!r}")
    for name, v in values.items():
        metrics[name] = {"value": float(v), "unit": units[name]}

    t_check = time.perf_counter()
    got = s.program_readings()
    params0, first = s.reference_inputs()
    s.free()
    del w
    log("zero input tiles (128 px x 128 ch) per conv, batch 0: " + ", ".join(
        f"{k}={v:.4f}" for k, v in zero_tiles(cell, params0, first[0]).items()))
    numbers = check.compare(got, run_reference(cell, params0, first))
    correct = (check.verdict(numbers, cell.limits) and failed == 0
               and len(window_losses) > 0)
    # A number with no limit in the cell's file is printed, not judged.
    checks = {k: {"value": numbers[k], "limit": cell.limits.get(k)}
              for k in check.NUMBERS}
    log(f"check_s={time.perf_counter() - t_check!r} (after the window, "
        f"not in setup_s)")
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    log(f"check window_nonfinite_losses: {failed} (limit 0)")
    checks["window_nonfinite_losses"] = {"value": failed, "limit": 0}
    out = {"correct": bool(correct), "attempted": len(window_losses),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
