"""The chip benchmark's trace reduction, on the CPU."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import flops, spec, tracing  # noqa: E402

HLO = """\
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(layer:conv1)/repro:derive:im2col:3/concatenate"}
  %repro_gemm_compact_1_0.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layer:conv1)/repro:gemm:compact:1:0/pallas_call"}
  %pad.2 = f32[8]{0} pad(%fusion.1), metadata={op_name="jit(step)/jvp(layer:conv1)/repro:gemm:compact:1:0/pad"}
  %repro_encode_act_2.1 = f32[8]{0} custom-call(%pad.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layer:conv2)/repro:encode:act:2/pallas_call"}
  %fusion.3 = f32[8]{0} fusion(%pad.2), kind=kLoop, metadata={op_name="jit(step)/jvp(layer:conv2)/mul"}
  %while.4 = f32[8]{0} while(%pad.2)
  ROOT %copy.5 = f32[8]{0} copy(%pad.2)
}
"""


def _ev(dev, name, start, dur):
    return {"dev": dev, "line": "XLA Ops", "name": name,
            "start_ns": float(start),
            "dur_ns": float(dur)}


def _host(name, start, dur):
    return {"host": name, "start_ns": float(start), "dur_ns": float(dur)}


def _ctx(events, steps=2):
    reduced = tracing.reduce(events, HLO)
    return tracing.Context(reduced=reduced, steps=steps,
                           window_s=reduced.window_s(), images=32, chips=1,
                           flops_per_image=1e9, peak_flops=1e12,
                           hbm_bytes=2 * 10**9)


SYNTHETIC = [
    _host("bench:window", 1000, 10000),
    _host("bench:dispatch", 1000, 500),
    _host("bench:wait", 1500, 9500),
    _ev(0, "fusion.1", 500, 1000),                 # clipped to 1000..1500
    _ev(0, "repro_gemm_compact_1_0.1", 2000, 3000),
    _ev(0, "pad.2", 5000, 1000),
    _ev(0, "repro_encode_act_2.1", 6000, 500),
    _ev(0, "while.4", 7000, 2000),                 # encloses the two below
    _ev(0, "fusion.3", 7000, 1500),
    _ev(0, "copy.5", 8000, 1000),                  # overlaps the fusion
    _ev(0, "copy.5", 12000, 500),                  # after the window
    dict(_ev(0, "jit_step", 1000, 9000), line="XLA Modules"),
]


def _reader(name):
    return spec.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                            "reader_" + name)


def test_reduction_of_a_synthetic_trace():
    ctx = _ctx(SYNTHETIC)
    r = ctx.reduced
    assert r.devices == [0]
    # busy: 1000-1500, 2000-6500, 7000-9000 = 500 + 4500 + 2000 ns
    assert r.busy_s() == pytest.approx(7000e-9)
    assert _reader("device_idle").read(ctx) == pytest.approx(30.0)
    assert _reader("gemm_ms").read(ctx) == pytest.approx(3000e-6 / 2)
    assert _reader("encode_ms").read(ctx) == pytest.approx(500e-6 / 2)
    assert _reader("dispatch_ms").read(ctx) == pytest.approx(1000e-6 / 2)
    assert _reader("derive_ms").read(ctx) == pytest.approx(500e-6 / 2)
    # fusion.3 and copy.5: under no scope that another reader takes.
    assert _reader("unscoped_ms").read(ctx) == pytest.approx(2500e-6 / 2)
    assert _reader("hbm_gb").read(ctx) == pytest.approx(2.0)
    # 1e9 FLOP x 32 images over 10 us, over 1e12 FLOP/s.
    assert _reader("mfu").read(ctx) == pytest.approx(
        100 * 1e9 * 32 / 1e-5 / 1e12)
    b = r.breakdown()
    assert b["device_ops"][0] == ["repro_gemm_compact_1_0.1 "
                                  "conv1/gemm:compact:1/pallas_call",
                                  pytest.approx(3e-6)]
    assert len(b["idle_gaps"]) == 3
    assert b["idle_gaps"][0] == ["bench:wait", pytest.approx(2e-6)]


def test_readers_find_nothing_in_an_empty_trace():
    ctx = _ctx([_host("bench:window", 0, 1000)])
    for name in ("gemm_ms", "encode_ms", "dispatch_ms", "derive_ms",
                 "unscoped_ms", "device_idle", "mfu"):
        assert _reader(name).read(ctx) is None, name


RECORDED = os.path.join(BENCH, "testdata", "vgg16.b16.letterbox.json")


def test_instruction_of_a_tpu_event_name():
    assert tracing.instruction(
        "%fusion.672 = s32[49]{0:T(128)S(1)} fusion(%iota.80), kind=kLoop, "
        "calls=%fused_computation.1851") == "fusion.672"
    assert tracing.instruction("fusion.1") == "fusion.1"


def test_reduction_of_a_recorded_chip_trace():
    """One step of vgg16.b16.letterbox as a TPU v5e traced it, cut down by
    record_trace.py: the reduction finds the line of HLO operations, every
    per-layer metric of the cell reads, and the five readers of device
    time split the step's busy time between them."""
    with open(RECORDED) as f:
        rec = json.load(f)
    reduced = tracing.reduce(rec["events"], rec["hlo"])
    assert reduced.line == "XLA Ops" and reduced.devices == [0]
    cell = spec.load_cell("vgg16.b16.letterbox")
    ctx = tracing.Context(
        reduced=reduced, steps=1, window_s=reduced.window_s(),
        images=cell.global_batch, chips=1,
        flops_per_image=flops.train_flops_per_image(cell.config),
        peak_flops=spec.peaks("TPU v5 lite")["bf16_flops_per_s"],
        hbm_bytes=13529866752)
    got = {name: r.read(ctx) for name, r in cell.readers().items()}
    assert got["gemm_ms"] == pytest.approx(140.026688)
    assert got["encode_ms"] == pytest.approx(1.619906)
    assert got["dispatch_ms"] == pytest.approx(37.883022)
    assert got["derive_ms"] == pytest.approx(0.849745)
    assert got["unscoped_ms"] == pytest.approx(218.387523)
    assert got["device_idle"] == pytest.approx(0.56054, abs=1e-4)
    assert got["mfu"] == pytest.approx(1.86148, abs=1e-4)
    parts = ("gemm_ms", "encode_ms", "dispatch_ms", "derive_ms",
             "unscoped_ms")
    assert sum(got[k] for k in parts) == pytest.approx(
        reduced.busy_s() * 1e3, rel=1e-4)
    top = reduced.breakdown()["device_ops"][0]
    assert top[0] == ("repro_gemm_compact_1_147.1 "
                      "conv2/gemm:compact:1/pallas_call")


def test_short_scope():
    assert tracing.short_scope(
        "jit(step)/transpose(jvp(layer:conv3))/repro:gemm:compact:1:12/"
        "pallas_call") == "conv3/gemm:compact:1/pallas_call"


def _tiny_traced_run(per_layer=None):
    import time

    import jax
    from chipbench import harness
    from chip_bench_tiny import tiny_cell

    cell = tiny_cell("vgg16.b16.letterbox")
    if per_layer is not None:
        cell.per_layer = [m for m in cell.per_layer if m["name"] in per_layer]
    return harness.run(cell, 17, 0.2, True, jax.devices("cpu"),
                       time.perf_counter())


def test_traced_run_on_the_cpu(monkeypatch):
    """A whole ``--trace 1`` run at a tiny size: the CPU's trace has no TPU
    plane, so of the cell's metrics only the compiler's footprint can be
    read there; with the others set aside the window and the check run."""
    from chipbench import harness

    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops_per_s": 1e12})
    out = _tiny_traced_run(per_layer={"hbm_gb"})
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == harness.TRACE_STEPS
    assert set(out["metrics"]) == {"hbm_gb"}
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 0
    assert out["breakdown"]["device_ops"] == []


def test_traced_run_fails_on_a_metric_it_cannot_read(monkeypatch):
    """A metric the cell lists and the trace does not hold fails the run
    and names the metric, rather than leaving it out of the line."""
    from chipbench import harness

    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops_per_s": 1e12})
    with pytest.raises(harness.MissingMetric, match="gemm_ms"):
        _tiny_traced_run()

