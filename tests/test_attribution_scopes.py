"""Every part of a training step lies under a scope that names it.

The program names where a step's device time goes with attribution scopes
(``repro:layout|pool|norm|merge|pad|scatter:``, ``kernels/stats.py``)
beside its bitmap-lifecycle ones; the chip benchmark's readers split the
step's time by them.  These tests walk the jaxpr of the tiny VGG16 and
ResNet-stage training steps (width 1/16, 32x32 images, batch 2, as the
chip benchmark's CPU tests build them) and check where each kind of work
lies, and that the static audit looks through the new scopes.
"""
import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

from chip_bench_tiny import tiny_cell  # noqa: E402
from chipbench import program  # noqa: E402
from repro.analysis import jaxpr_audit  # noqa: E402
from repro.kernels import stats  # noqa: E402
from repro.models.cnn import build_cnn  # noqa: E402

WORKLOADS = ["vgg16.b16.letterbox", "resnet18_block2.b64.letterbox"]
TAG = re.compile(r"repro:([^/():]+)[^/()]*")
LAYOUT_STAGE = re.compile(r"repro:layout:(\w+):")


def _inputs(workload):
    cfg = tiny_cell(workload).config
    model = build_cnn(cfg["net"], image_size=cfg["image_size"],
                      width=cfg["width"], num_classes=cfg["num_classes"])
    params = model.init(jax.random.PRNGKey(0))
    images = jnp.zeros((2, cfg["image_size"], cfg["image_size"], 3))
    labels = jnp.zeros((2,), jnp.int32)
    return cfg, params, images, labels


def _walk(jaxpr, outer=""):
    """(equation, its whole scope path) over a jaxpr and its sub-jaxprs,
    Pallas kernel bodies left out."""
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        yield eqn, stack
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, stack)


@functools.lru_cache(maxsize=None)
def _step(workload):
    """([(primitive, [scope kinds, outermost first], layer)], {scope tags})
    of the workload's whole training step: value_and_grad and the SGD
    update, as the chip benchmark compiles it."""
    cfg, *args = _inputs(workload)
    jx = jax.make_jaxpr(program.build_step(cfg))(*args)
    rows, tags = [], set()
    for eqn, stack in _walk(jx.jaxpr):
        layers = re.findall(r"layer:([^/()]+)", stack)
        found = list(TAG.finditer(stack))
        rows.append((eqn.primitive.name, [m.group(1) for m in found],
                     layers[-1] if layers else ""))
        tags.update(m.group(0) for m in found)
    return rows, frozenset(tags)


def _step_eqns(workload):
    return _step(workload)[0]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_pools_lie_under_pool_scopes(workload):
    rows = _step_eqns(workload)
    pools = [(p, k) for p, k, _ in rows
             if p.startswith("reduce_window") or p.startswith("select_and")]
    assert all("pool" in k for _, k in pools), pools
    # VGG16's five max pools; both nets end in the global average pool.
    assert len(pools) == (0 if workload.startswith("resnet") else 10)
    assert any(p == "reduce_sum" and "pool" in k for p, k, _ in rows)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_batchnorm_lies_under_norm_scopes(workload):
    rsqrt = [k for p, k, _ in _step_eqns(workload) if p == "rsqrt"]
    assert all(k[-1] == "norm" for k in rsqrt), rsqrt
    # VGG16 (configuration D) has no BatchNorm; the ResNet stage has six.
    assert len(rsqrt) == (6 if workload.startswith("resnet") else 0)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_conv_engine_layout_lies_under_layout_scopes(workload):
    """Every concatenate and slice of a conv layer that no lifecycle scope
    holds is the engine's re-layout, and is named so; all three stages
    have some."""
    loose = []
    for p, kinds, layer in _step_eqns(workload):
        if not layer or layer == "head":
            continue
        lifecycle = any(k in stats.LIFECYCLE_KINDS for k in kinds)
        if p in ("concatenate", "slice") and not lifecycle \
                and "layout" not in kinds:
            loose.append((p, kinds, layer))
    assert loose == []
    stages = {m.group(1) for tag in _step(workload)[1]
              for m in [LAYOUT_STAGE.match(tag)] if m}
    assert {"fp", "bp", "wg", "lift"} <= stages, stages


@pytest.mark.parametrize("workload", WORKLOADS)
def test_compact_scatter_lies_under_scatter_scopes(workload):
    adds = [k for p, k, _ in _step_eqns(workload)
            if p == "scatter-add" and "gemm" in k]
    assert adds, "the compact schedule scatters its queue"
    assert all(k[-1] == "scatter" for k in adds), adds


@pytest.mark.parametrize("workload", WORKLOADS)
def test_merges_lie_under_merge_scopes(workload):
    kinds = {k for _, ks, _ in _step_eqns(workload) for k in ks}
    has_branches = workload.startswith("resnet")
    assert ("merge" in kinds) == has_branches
    assert {"layout", "pool", "pad", "scatter"} <= kinds


@pytest.mark.parametrize("workload", WORKLOADS)
def test_no_attribution_scope_encloses_a_lifecycle_scope(workload):
    bad = []
    for p, kinds, layer in _step_eqns(workload):
        for i, k in enumerate(kinds):
            if k in stats.ATTRIBUTION_KINDS and any(
                    j in stats.LIFECYCLE_KINDS for j in kinds[i + 1:]):
                bad.append((p, kinds, layer))
                break
    assert bad == []


def _audit(workload):
    cfg, params, images, labels = _inputs(workload)
    from repro.core import policy as pol
    model = build_cnn(cfg["net"], image_size=cfg["image_size"],
                      width=cfg["width"], num_classes=cfg["num_classes"])
    policy = getattr(pol, cfg["policy"]).with_(
        kernel_impl=cfg["kernel_impl"])
    vs = jaxpr_audit.audit_fn(
        lambda: jax.grad(
            lambda p: model.loss(p, images, labels, policy))(params),
        workload=workload)
    # Scope instance numbers differ from one trace to the next.
    return [(v.code, v.where, re.sub(r"(repro:[^ ]*?):\d+", r"\1",
                                     v.message)) for v in vs]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_audit_verdicts_do_not_change_with_attribution_scopes(
        workload, monkeypatch):
    """The static audit reports the same violations on the step with its
    attribution scopes as on the same step traced without them (none, on
    these steps)."""
    with_scopes = _audit(workload)
    scope = stats.lifecycle_scope

    def lifecycle_only(kind, detail=""):
        if kind in stats.ATTRIBUTION_KINDS:
            return contextlib.nullcontext()
        return scope(kind, detail)

    monkeypatch.setattr(stats, "lifecycle_scope", lifecycle_only)
    without = _audit(workload)
    assert with_scopes == without == []


def test_audit_region_is_the_innermost_lifecycle_tag():
    """A pad nested in a gemm region stays in that region, and a layout
    scope grounds nothing: the audit looks through attribution tags."""
    def f(x):
        with stats.lifecycle_scope("gemm", "compact:1"):
            with stats.lifecycle_scope("pad", "compact"):
                y = jnp.sin(x)
        with stats.lifecycle_scope("layout", "fp"):
            return jnp.cos(y)

    walk = jaxpr_audit._Walk(jax.make_jaxpr(f)(jnp.ones(4)))
    assert [i.tag.kind if i.tag else None for i in walk.infos] == \
        ["gemm", None]


def test_scope_kinds_are_closed():
    with pytest.raises(ValueError, match="unknown scope kind"):
        stats.lifecycle_scope("layuot", "fp")
