"""The reader of exposed collective time, ``allreduce_exposed_ms``, on the
CPU: on synthetic traces, and on one step of the four-chip cell as a
TPU v5e traced it (``testdata/vgg16.dp4.letterbox.json``, cut by
``record_trace.py``)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import flops, spec, tracing  # noqa: E402
from record_trace import hlo_line  # noqa: E402

READER = spec.load_module(os.path.join(BENCH, "metrics",
                                       "allreduce_exposed_ms.py"),
                          "collectives_reader")

OPS = [
    ("fusion.1", "jit(step)/jvp(layer:conv1)/mul", False),
    ("all-reduce.99", "jit(step)/jit(body)/shard_map/"
     "repro:collective:dense:156/psum", False),
    # What XLA's combiner builds carries no scope.
    ("all-reduce.7", "", False),
    ("all-reduce-start.2", "", False),
    ("all-reduce-done.2", "", False),
    ("copy.3", "", False),
    ("while.4", "", False),
    ("repro_gemm_compact_1_0.1", "jit(step)/jvp(layer:conv1)/"
     "repro:gemm:compact:1:0/pallas_call", True),
]
HLO = "ENTRY %main {\n" + "\n".join(hlo_line(*o) for o in OPS) + "\n}"


def _ctx(events_by_dev, steps=1, window=(0, 100000)):
    events = [{"host": "bench:window", "start_ns": float(window[0]),
               "dur_ns": float(window[1] - window[0])}]
    for dev, evs in events_by_dev.items():
        events += [{"dev": dev, "line": "XLA Ops", "name": n,
                    "start_ns": float(s), "dur_ns": float(e - s)}
                   for n, s, e in evs]
    reduced = tracing.reduce(events, HLO)
    return tracing.Context(reduced=reduced, steps=steps,
                           window_s=reduced.window_s(), images=64, chips=4,
                           flops_per_image=1e9, peak_flops=1e12,
                           hbm_bytes=10**9)


MS = 1e-6   # ms per ns


def test_a_collective_under_compute_reads_nothing_of_its_flight():
    """Compute that covers an asynchronous all-reduce's whole flight hides
    it: only its own start and done ops are left exposed."""
    ctx = _ctx({0: [("all-reduce-start.2", 1000, 1100),
                    ("fusion.1", 1100, 2900),
                    ("all-reduce-done.2", 2900, 3000)]})
    assert READER.read(ctx) == pytest.approx(200 * MS)


def test_a_loop_around_a_collective_does_not_hide_it():
    """An op that encloses others (a loop whose body is traced too) is not
    compute that overlaps them."""
    ctx = _ctx({0: [("while.4", 0, 4000), ("fusion.1", 0, 1000),
                    ("all-reduce.99", 1000, 3000), ("copy.3", 3000, 4000)]})
    assert READER.read(ctx) == pytest.approx(2000 * MS)


def test_a_collective_that_nothing_overlaps_reads_its_length():
    ctx = _ctx({0: [("fusion.1", 0, 1000), ("all-reduce.99", 1000, 3000),
                    ("repro_gemm_compact_1_0.1", 3000, 4000)]})
    assert READER.read(ctx) == pytest.approx(2000 * MS)


def test_a_combined_all_reduce_without_a_scope_counts():
    ctx = _ctx({0: [("all-reduce.7", 5000, 5600), ("copy.3", 5400, 6000)]})
    assert READER.read(ctx) == pytest.approx(400 * MS)


def test_an_async_pair_counts_from_start_to_done_less_what_it_hides():
    ctx = _ctx({0: [("all-reduce-start.2", 6000, 6100),
                    ("fusion.1", 6100, 6500),
                    ("all-reduce-done.2", 6800, 7000)]})
    assert READER.read(ctx) == pytest.approx((1000 - 400) * MS)


def test_per_step_averaged_over_chips():
    ctx = _ctx({0: [("all-reduce.99", 0, 1000)],
                1: [("all-reduce.99", 0, 3000)],
                2: [("fusion.1", 0, 500)],
                3: [("all-reduce.99", 0, 1000), ("fusion.1", 0, 1000)]},
               steps=2)
    assert READER.read(ctx) == pytest.approx((1000 + 3000) / 4 / 2 * MS)


def test_no_collective_reads_nothing():
    ctx = _ctx({0: [("fusion.1", 0, 1000), ("copy.3", 1000, 2000)]})
    assert READER.read(ctx) is None
    assert READER.read(_ctx({})) is None


def test_reduction_of_a_recorded_four_chip_step():
    """One step of vgg16.dp4.letterbox as a TPU v5e host with four chips
    traced it: XLA combined the 14 gradient leaves and the loss into one
    synchronous ``all-reduce.99`` on each chip, which nothing overlaps; the
    reader takes its mean over the chips, every per-layer metric of the
    cell reads, and the five readers of device time still split the busy
    time between them (the all-reduce inside ``unscoped_ms``)."""
    with open(os.path.join(BENCH, "testdata",
                           "vgg16.dp4.letterbox.json")) as f:
        rec = json.load(f)
    reduced = tracing.reduce(rec["events"], rec["hlo"])
    assert reduced.line == "XLA Ops" and reduced.devices == [0, 1, 2, 3]
    cell = spec.load_cell("vgg16.dp4.letterbox")
    ctx = tracing.Context(
        reduced=reduced, steps=1, window_s=reduced.window_s(),
        images=cell.global_batch, chips=cell.chips,
        flops_per_image=flops.train_flops_per_image(cell.config),
        peak_flops=spec.peaks("TPU v5 lite")["bf16_flops_per_s"],
        hbm_bytes=9632434688)
    got = {name: r.read(ctx) for name, r in cell.readers().items()}
    assert all(v is not None for v in got.values()), got
    reduces = reduced.seconds(lambda op: op.name == "all-reduce.99") * 1e3
    assert got["allreduce_exposed_ms"] == pytest.approx(reduces)
    assert got["allreduce_exposed_ms"] == pytest.approx(1.05902775)
    assert got["gemm_ms"] == pytest.approx(140.50372325)
    parts = ("gemm_ms", "encode_ms", "dispatch_ms", "derive_ms",
             "unscoped_ms")
    assert sum(got[k] for k in parts) == pytest.approx(
        reduced.busy_s() * 1e3, rel=1e-4)
    assert got["unscoped_ms"] > got["allreduce_exposed_ms"]
