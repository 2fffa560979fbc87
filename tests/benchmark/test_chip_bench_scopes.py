"""The readers of the program's attribution scopes, on the CPU.

``layout_ms``, ``pool_ms``, ``norm_ms`` and ``merge_ms`` split what
``unscoped_ms`` reads; ``pad_ms`` and ``scatter_ms`` split what
``dispatch_ms`` reads.  Checked on a synthetic trace and on one step of
each of two cells as a TPU v5e traced the program with those scopes
(``testdata/<cell>.scoped.json``, cut by ``record_trace.py``).
"""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import flops, spec, tracing  # noqa: E402
from record_trace import hlo_line  # noqa: E402

OLD = ("gemm_ms", "encode_ms", "dispatch_ms", "derive_ms", "unscoped_ms")
UNSCOPED_PARTS = ("layout_ms", "pool_ms", "norm_ms", "merge_ms")
DISPATCH_PARTS = ("pad_ms", "scatter_ms")
NEW = UNSCOPED_PARTS + DISPATCH_PARTS


def _reader(name):
    return spec.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                            "scopes_reader_" + name)


def _hlo(ops):
    return "ENTRY %main {\n" + "\n".join(
        hlo_line(name, scope, pallas) for name, scope, pallas in ops) + "\n}"


SCOPED = [
    ("fusion.1", "jit(step)/jvp(layer:conv1)/repro:layout:fp:0/concatenate",
     False),
    ("fusion.2", "jit(step)/jvp(layer:conv1)/repro:derive:im2col:3/pad",
     False),
    ("pad.3", "jit(step)/jvp(layer:conv1)/repro:gemm:compact:1:4/"
     "repro:pad:compact:1/pad", False),
    ("repro_gemm_compact_1_4.1", "jit(step)/jvp(layer:conv1)/"
     "repro:gemm:compact:1:4/pallas_call", True),
    ("scatter.4", "jit(step)/jvp(layer:conv1)/repro:gemm:compact:1:4/"
     "repro:scatter:compact:2/scatter-add", False),
    ("fusion.5", "jit(step)/jvp(layer:conv1)/repro:gemm:compact:1:4/"
     "repro:queue:prefix_sum:5/cumsum", False),
    ("fusion.6", "jit(step)/transpose(jvp(repro:pool:max:3))/"
     "select_and_scatter_add", False),
    ("fusion.7", "jit(step)/transpose(jvp(layer:conv2))/"
     "transpose(jvp(repro:norm:bn:4))/mul", False),
    ("add.8", "jit(step)/jvp(repro:merge:add:5)/add", False),
    ("copy.9", "jit(step)/transpose(jvp(layer:conv1))/"
     "repro:layout:wg:6/transpose", False),
    ("fusion.10", "jit(step)/sub", False),
]
# (start ns, duration ns) of each op above, in one step of 10 us.
TIMES = [(0, 1000), (1000, 100), (1100, 400), (1500, 3000), (4500, 300),
         (4800, 200), (5000, 700), (5700, 900), (6600, 250), (6850, 150),
         (7000, 50)]


def _ctx(ops, times, steps=1):
    events = [{"host": "bench:window", "start_ns": 0.0, "dur_ns": 10000.0}]
    events += [{"dev": 0, "line": "XLA Ops", "name": name,
                "start_ns": float(s), "dur_ns": float(d)}
               for (name, _, _), (s, d) in zip(ops, times)]
    reduced = tracing.reduce(events, _hlo(ops))
    return tracing.Context(reduced=reduced, steps=steps,
                           window_s=reduced.window_s(), images=16, chips=1,
                           flops_per_image=1e9, peak_flops=1e12,
                           hbm_bytes=10**9)


def _read(ctx, names):
    return {n: _reader(n).read(ctx) for n in names}


def test_readers_of_a_synthetic_trace():
    got = _read(_ctx(SCOPED, TIMES), OLD + NEW)
    ms = 1e-6
    assert got["layout_ms"] == pytest.approx((1000 + 150) * ms)
    assert got["pool_ms"] == pytest.approx(700 * ms)
    assert got["norm_ms"] == pytest.approx(900 * ms)
    assert got["merge_ms"] == pytest.approx(250 * ms)
    assert got["pad_ms"] == pytest.approx(400 * ms)
    assert got["scatter_ms"] == pytest.approx(300 * ms)
    # The queue build stays in dispatch_ms and in neither of its parts; the
    # unscoped loss op stays in unscoped_ms and in none of its parts.
    assert got["dispatch_ms"] == pytest.approx((400 + 300 + 200) * ms)
    assert got["unscoped_ms"] == pytest.approx(
        (1000 + 700 + 900 + 250 + 150 + 50) * ms)
    assert sum(got[k] for k in UNSCOPED_PARTS) == pytest.approx(
        got["unscoped_ms"] - 50 * ms)


def test_a_cell_without_a_kind_reads_nothing():
    """A program with attribution scopes but no BatchNorm has nothing for
    ``norm_ms`` to read: the reader finds nothing, which fails a run that
    lists the metric (so the metric lists the cells that have the kind)."""
    ops = [o for o in SCOPED if "norm" not in o[1]]
    times = [t for o, t in zip(SCOPED, TIMES) if "norm" not in o[1]]
    got = _read(_ctx(ops, times), NEW)
    assert got["norm_ms"] is None
    assert all(got[k] is not None for k in NEW if k != "norm_ms")


def test_a_program_without_attribution_scopes_reads_zero():
    """The program before these scopes (the parent of the change that
    brought them) attributes none of its time to them: every new reader
    reads 0 there, while the old readers read what they always did."""
    bare = [(name, re.sub(r"repro:(layout|pool|norm|merge|pad|scatter):"
                          r"[^/()]*/?", "", scope), pallas)
            for name, scope, pallas in SCOPED]
    old = _read(_ctx(SCOPED, TIMES), OLD)
    got = _read(_ctx(bare, TIMES), OLD + NEW)
    assert all(got[k] == 0.0 for k in NEW), got
    assert all(got[k] == pytest.approx(old[k]) for k in OLD)


def test_an_empty_trace_reads_nothing():
    ctx = tracing.Context(
        reduced=tracing.reduce(
            [{"host": "bench:window", "start_ns": 0.0, "dur_ns": 1.0}], ""),
        steps=1, window_s=1e-9, images=1, chips=1, flops_per_image=1.0,
        peak_flops=1.0, hbm_bytes=1)
    assert all(v is None for v in _read(ctx, NEW).values())


# One step of each cell as a TPU v5e traced it, and what each new reader
# reads there (ms).
RECORDED = {
    "vgg16.b16.letterbox": {
        "layout_ms": 150.942133, "pool_ms": 11.023554, "norm_ms": None,
        "merge_ms": None, "pad_ms": 27.373072, "scatter_ms": 10.217555},
    "resnet18_block2.b64.letterbox": {
        "layout_ms": 164.27442, "pool_ms": 0.612003, "norm_ms": 10.980305,
        "merge_ms": 1.028461, "pad_ms": 7.089239, "scatter_ms": 5.473026},
}


def _recorded(cell_name):
    with open(os.path.join(BENCH, "testdata",
                           cell_name + ".scoped.json")) as f:
        rec = json.load(f)
    reduced = tracing.reduce(rec["events"], rec["hlo"])
    cell = spec.load_cell(cell_name)
    ctx = tracing.Context(
        reduced=reduced, steps=1, window_s=reduced.window_s(),
        images=cell.global_batch, chips=1,
        flops_per_image=flops.train_flops_per_image(cell.config),
        peak_flops=spec.peaks("TPU v5 lite")["bf16_flops_per_s"],
        hbm_bytes=1)
    return cell, reduced, ctx


@pytest.mark.parametrize("cell_name", sorted(RECORDED))
def test_reduction_of_a_recorded_scoped_trace(cell_name):
    """The five old readers still split the step's busy time between them;
    the new ones read their recorded values, the four parts sit inside
    ``unscoped_ms`` and the two inside ``dispatch_ms``.  What the four
    parts leave of ``unscoped_ms`` is, but for under half a millisecond
    (the loss, the SGD update), ops that carry no ``op_name`` at all:
    fusions and copies that XLA built without the metadata of the ops they
    hold."""
    cell, reduced, ctx = _recorded(cell_name)
    got = _read(ctx, OLD + NEW)
    assert sum(got[k] for k in OLD) == pytest.approx(
        reduced.busy_s() * 1e3, rel=1e-4)
    for name, want in RECORDED[cell_name].items():
        assert got[name] == (None if want is None else pytest.approx(want)), \
            name
    parts = sum(got[k] for k in UNSCOPED_PARTS if got[k] is not None)
    unnamed = reduced.seconds(lambda op: not op.scope) * 1e3
    assert parts <= got["unscoped_ms"] * (1 + 1e-9)
    assert 0 <= got["unscoped_ms"] - parts - unnamed < 0.5
    assert got["pad_ms"] + got["scatter_ms"] <= got["dispatch_ms"]
    # Each metric the cell lists reads something; norm_ms and merge_ms,
    # which VGG16 has no op for, are listed for the ResNet cell only.
    listed = {m["name"] for m in cell.per_layer}
    assert all(got[k] is not None for k in NEW if k in listed)
    if cell_name.startswith("vgg16"):
        assert got["norm_ms"] is None and got["merge_ms"] is None
        assert not {"norm_ms", "merge_ms"} & listed
