"""The profiler trace of a window, reduced to what the metrics read.

``capture`` runs a body under ``jax.profiler`` and returns the trace's
events as plain records; ``reduce`` keeps the device operations inside the
window and the host's spans; the readers in ``metrics/`` take their numbers
from the result through ``Context``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Tuple, TypeVar

import jax

HOST_WINDOW = "bench:window"
HOST_STEP = "bench:step"
HOST_DISPATCH = "bench:dispatch"
HOST_WAIT = "bench:wait"

T = TypeVar("T")


def capture(body: Callable[[], T]) -> Tuple[List[dict], T]:
    """Trace ``body``; returns (event records, what the body returned)."""
    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation(HOST_WINDOW):
                out = body()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return load_events(paths[0]), out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def load_events(path: str) -> List[dict]:
    """The device events and the benchmark's own host spans of one
    ``.xplane.pb``, as plain records:

      {"dev": <device id>, "line": <line name>, "name", "start_ns", "dur_ns"}
      {"host": <span name>, "start_ns", "dur_ns"}

    Device events come from every line of each ``/device:TPU:<id>``
    plane (on a v5e: ``Steps``, ``XLA Modules``, ``XLA Ops``, ``Async XLA
    Ops``), named by their HLO instruction; ``Reduced`` keeps the line of
    HLO operations (``XLA Ops``).
    Host spans are the ``bench:*`` annotations on the host's planes.  Both
    count from the profile's start, on one clock."""
    from jax.profiler import ProfileData
    out: List[dict] = []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                for e in line.events:
                    out.append({"dev": dev, "line": line.name,
                                "name": instruction(e.name),
                                "start_ns": float(e.start_ns),
                                "dur_ns": float(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out.append({"host": e.name,
                                    "start_ns": float(e.start_ns),
                                    "dur_ns": float(e.duration_ns)})
    return out


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench:"


def instruction(event_name: str) -> str:
    """The HLO instruction an operation event names.  A TPU trace names
    each one by its whole line of HLO text,
    ``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop, calls=...``, without
    its metadata; the instruction is what precedes `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def ops_line(events: List[dict], ops: Dict[str, Tuple[str, bool]]) -> str:
    """The device line whose events are HLO operations: the one with the
    most events named after an instruction of the compiled module."""
    hits: Dict[str, int] = {}
    for e in events:
        if "dev" in e and e["name"] in ops:
            hits[e["line"]] = hits.get(e["line"], 0) + 1
    return max(hits, key=hits.get) if hits else ""


def hlo_ops(hlo_text: str) -> Dict[str, Tuple[str, bool]]:
    """HLO instruction name -> (op_name scope path, is a Pallas kernel),
    from the compiled module's text.  A Pallas kernel is a custom call to
    ``tpu_custom_call``; the op_name carries the ``jax.named_scope`` path
    (``.../layer:conv3/repro:gemm:compact:1:12/pallas_call``); a fusion
    carries its root's."""
    out: Dict[str, Tuple[str, bool]] = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = (op.group(1) if op else "",
                           'custom_call_target="tpu_custom_call"' in line)
    return out


@dataclasses.dataclass
class Op:
    dev: int
    name: str
    start: float          # ns from the profile's start
    end: float
    scope: str
    pallas: bool

    @property
    def dur(self) -> float:
        return self.end - self.start


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _leaves(ops: List[Op]) -> List[Op]:
    """Drop an op that encloses others on its device (a loop or a call
    whose body is traced too), so that no time counts twice."""
    keep: List[Op] = []
    stack: List[Op] = []
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1].end <= op.start:
            keep.append(stack.pop())
        if stack and op.end <= stack[-1].end:
            stack[-1] = dataclasses.replace(stack[-1], scope="\0enclosing")
        stack.append(op)
    keep.extend(stack)
    return [o for o in keep if o.scope != "\0enclosing"]


class Reduced:
    """Device operations inside the traced window, per device, with the
    host spans that say what the host was doing."""

    def __init__(self, events: List[dict], ops: Dict[str, Tuple[str, bool]]):
        window = [e for e in events if e.get("host") == HOST_WINDOW]
        if len(window) != 1:
            raise ValueError(f"expected one {HOST_WINDOW} span, found "
                             f"{len(window)}")
        self.t0 = window[0]["start_ns"]
        self.t1 = self.t0 + window[0]["dur_ns"]
        self.host = [e for e in events if "host" in e and e is not window[0]]
        self.lines = sorted({e["line"] for e in events if "dev" in e})
        self.line = ops_line(events, ops)
        by_dev: Dict[int, List[Op]] = {}
        for e in events:
            if e.get("line") != self.line or "dev" not in e:
                continue
            s = max(e["start_ns"], self.t0)
            t = min(e["start_ns"] + e["dur_ns"], self.t1)
            if t <= s:
                continue
            scope, pallas = ops.get(e["name"], ("", False))
            by_dev.setdefault(e["dev"], []).append(
                Op(e["dev"], e["name"], s, t, scope, pallas))
        self.devices = sorted(by_dev)
        self.all_ops = by_dev
        self.ops = {d: _leaves(v) for d, v in by_dev.items()}

    def busy_s(self) -> float:
        """Union of the op intervals, seconds, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices:
            tot += sum(e - s for s, e in _union(
                [(o.start, o.end) for o in self.all_ops[d]]))
        return tot / len(self.devices) / 1e9

    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def seconds(self, pred: Callable[[Op], bool]) -> float:
        """Device time of the ops ``pred`` picks, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(o.dur for d in self.devices for o in self.ops[d]
                   if pred(o)) / len(self.devices) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (summed over the window by
        instruction and scope, averaged over devices) and the longest idle
        gaps of the first device, by the host span that covered most of
        each."""
        per: Dict[str, float] = {}
        for d in self.devices:
            for o in self.ops[d]:
                label = f"{o.name} {short_scope(o.scope)}".strip()
                per[label] = per.get(label, 0.0) + o.dur / 1e9
        n = max(len(self.devices), 1)
        device_ops = sorted(([k, v / n] for k, v in per.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.devices:
            busy = _union([(o.start, o.end)
                           for o in self.all_ops[self.devices[0]]])
            edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append([self._host_at(a, b), (b - a) / 1e9])
        gaps.sort(key=lambda kv: -kv[1])
        return {"device_ops": device_ops, "idle_gaps": gaps[:top]}

    def _host_at(self, a: float, b: float) -> str:
        best, name = 0.0, "host:untraced"
        for h in self.host:
            if h["host"] == HOST_STEP:
                continue
            ov = min(b, h["start_ns"] + h["dur_ns"]) - max(a, h["start_ns"])
            if ov > best:
                best, name = ov, h["host"]
        return name


def short_scope(scope: str) -> str:
    """``jit(step)/transpose(jvp(layer:conv3))/repro:gemm:compact:1:12/
    pallas_call`` -> ``conv3/gemm:compact:1/pallas_call``: the layer and
    the kind, without the instance number."""
    parts = []
    for p in scope.split("/"):
        m = re.search(r"layer:([\w.\-]+)", p)
        if m:
            parts.append(m.group(1))
            continue
        m = re.search(r"repro:([\w:]+?)(?::\d+)?\)*$", p)
        if m:
            parts.append(m.group(1))
        elif p in ("pallas_call",):
            parts.append(p)
    return "/".join(parts)


def in_scope(op: Op, *kinds: str) -> bool:
    """The op lies under a ``repro:<kind>:`` lifecycle scope."""
    return any(f"repro:{k}:" in op.scope for k in kinds)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    reduced: Reduced
    steps: int
    window_s: float
    images: int
    chips: int
    flops_per_image: float
    peak_flops: float
    hbm_bytes: int

    def per_step_ms(self, pred: Callable[[Op], bool]) -> Optional[float]:
        """Device ms per step of the ops ``pred`` picks; None where the
        trace has no such op."""
        if not any(pred(o) for d in self.reduced.devices
                   for o in self.reduced.ops[d]):
            return None
        return self.reduced.seconds(pred) * 1e3 / self.steps


def reduce(events: List[dict], hlo_text: str) -> Reduced:
    return Reduced(events, hlo_ops(hlo_text))
