"""Static lifecycle audit: prove the bitmap contract on a traced jaxpr.

``jax.make_jaxpr`` gives the full dataflow of a training step without
executing a kernel; the runtime leaves machine-readable breadcrumbs in it
via ``kernels.stats.lifecycle_scope`` (scope names survive into every
equation's ``source_info.name_stack``, including through jvp/transpose).
This module walks that jaxpr and checks, per activation:

  RESCAN           the same tensor is scanned/encoded for a bitmap more
                   than once per step (the paper's contract: ONE fused
                   encode per activation; every later mask is derived).
  UNDERIVED_MASK   an integer mask enters a GEMM dispatch without being
                   reachable from an encode/scan/derive/queue region
                   through pure bitmap arithmetic — i.e. somebody computed
                   sparsity metadata outside the sanctioned producers.
  DENSE_GEMM       a ``dot_general`` outside any ``sparse_gemm`` dispatch
                   region — dense compute leaked onto the hot path.
  DENSE_SCHEDULE   a dispatch region resolved to ``schedule="dense"`` while
                   the audit expects the Pallas path.
  CONV_FALLBACK    a ``conv_general_dilated`` on the traced path: inside
                   the counted fallback region it means a layer escaped the
                   engine; outside any region it is an uncounted dense conv.
  SPEC_UNRESOLVED  a ``sparse_gemm`` dispatch whose ``GemmSpec`` was not
                   resolved by ``SparsityPolicy.gemm_spec`` (trace-time
                   provenance via ``kernels.ops.collect_gemm_events``).
  COLLECTIVE_UNTAGGED  a cross-shard collective (psum/all_gather/…)
                   outside any ``repro:collective:*`` region — gradient
                   traffic crossed the mesh without going through
                   ``sharding/collectives``'s bitmap-aware entry points.

All checks apply INSIDE ``shard_map`` bodies too: the generic sub-jaxpr
descent picks up the ``shard_map`` equation's ``jaxpr`` param like any
pjit/cond/scan, so the one-encode-per-activation and mask-derivation
contracts are verified across the whole mesh (the body is traced once for
all shards — one encode in the jaxpr IS one encode per shard per step).

Violations are keyed by the innermost ``layer:<name>`` scope so reports
read per-layer.  See docs/static_analysis.md for the full code catalogue.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.stats import LIFECYCLE_KINDS

from .report import Violation

# One scope tag: repro:<kind>[:<detail>]:<seq>.  Tags never contain
# "/", "(" or ")", which is exactly what the name-stack string uses for
# nesting and transform wrappers — so this match always grabs a whole tag.
# An equation's region is its innermost LIFECYCLE tag: attribution tags
# (layout, pool, norm, merge, pad, scatter) only say where device time
# goes, so a pad nested in a gemm region stays in that region and a layout
# scope around data preparation grounds nothing.
TAG_RE = re.compile(r"repro:([^/():]+)[^/()]*")
LAYER_RE = re.compile(r"layer:[^/()]+")

# Primitives that forward bitmap/array content without computing anything
# new from it — a mask may flow through these on its way from a producer
# region to a consumer without breaking derivation provenance.
TRIVIAL_PRIMS = {
    "convert_element_type", "reshape", "transpose", "squeeze",
    "expand_dims", "broadcast_in_dim", "slice", "dynamic_slice", "pad",
    "concatenate", "copy", "stop_gradient", "rev",
}

# "collective" grounds masks too: the union bitmap a bitmap-psum produces
# is derived metadata (an OR across shards of already-grounded bitmaps).
GROUNDING_KINDS = {"encode", "scan", "derive", "queue", "collective"}

# Cross-shard primitives that move tensor data over the interconnect.
COLLECTIVE_PRIMS = {
    "psum", "psum2", "all_reduce", "all_gather", "all_to_all",
    "reduce_scatter", "ppermute", "pmax", "pmin",
}


@dataclasses.dataclass
class ParsedTag:
    kind: str          # one of stats.LIFECYCLE_KINDS
    detail: str        # e.g. "act", "grad", "compact:1"
    tag: str           # the full unique tag (region identity)


def parse_tag(tag: str) -> ParsedTag:
    parts = tag.split(":")
    # repro:<kind>[:<detail>...]:<seq>
    return ParsedTag(kind=parts[1], detail=":".join(parts[2:-1]), tag=tag)


@dataclasses.dataclass
class EqnInfo:
    eqn: Any
    tag: Optional[ParsedTag]      # innermost lifecycle region, if any
    layer: str                    # innermost layer:<name> scope, or ""
    depth: int                    # sub-jaxpr nesting depth


class _Walk:
    """Flattened equation list over a closed jaxpr and its sub-jaxprs,
    with a best-effort var-aliasing map across jaxpr boundaries."""

    def __init__(self, closed_jaxpr):
        self.infos: List[EqnInfo] = []
        self.producer: Dict[Any, EqnInfo] = {}
        self.alias: Dict[Any, Any] = {}
        self._visit(closed_jaxpr.jaxpr, outer_stack="", depth=0)

    # -- var canonicalization across sub-jaxpr boundaries --
    def canon(self, v):
        seen = set()
        while v in self.alias and v not in seen:
            seen.add(v)
            v = self.alias[v]
        return v

    def _link(self, inner_vars, outer_vars):
        if len(inner_vars) != len(outer_vars):
            return  # unknown convention: leave unaliased (conservative)
        for iv, ov in zip(inner_vars, outer_vars):
            if type(iv).__name__ == "Var" and type(ov).__name__ == "Var":
                self.alias[iv] = ov

    @staticmethod
    def _sub_jaxprs(eqn):
        """(jaxpr, invars_of_eqn_feeding_it) pairs found in eqn params."""
        if eqn.primitive.name == "pallas_call":
            return []  # kernel-internal program: the sanitizer's domain
        subs = []

        def collect(v):
            core_jaxpr = getattr(v, "jaxpr", None)
            if core_jaxpr is not None and hasattr(core_jaxpr, "eqns"):
                subs.append(core_jaxpr)          # ClosedJaxpr
            elif hasattr(v, "eqns"):
                subs.append(v)                   # raw Jaxpr
            elif isinstance(v, (list, tuple)):
                for x in v:
                    collect(x)

        for v in eqn.params.values():
            collect(v)
        return subs

    def _visit(self, jaxpr, outer_stack: str, depth: int):
        for eqn in jaxpr.eqns:
            stack = outer_stack + "/" + str(eqn.source_info.name_stack)
            tags = [m.group(0) for m in TAG_RE.finditer(stack)
                    if m.group(1) in LIFECYCLE_KINDS]
            layers = LAYER_RE.findall(stack)
            info = EqnInfo(
                eqn=eqn,
                tag=parse_tag(tags[-1]) if tags else None,
                layer=layers[-1][len("layer:"):] if layers else "",
                depth=depth,
            )
            self.infos.append(info)
            for ov in eqn.outvars:
                self.producer[ov] = info
            for sub in self._sub_jaxprs(eqn):
                # Common conventions (pjit/closed_call/custom_*) line the
                # eqn invars up 1:1 with the sub-jaxpr invars; cond carries
                # the predicate first.  Anything else stays unaliased.
                inv = list(eqn.invars)
                if eqn.primitive.name == "cond" and inv:
                    inv = inv[1:]
                self._link(list(sub.invars), inv)
                self._link(list(eqn.outvars), list(sub.outvars))
                self._visit(sub, stack, depth + 1)


def _is_var(v) -> bool:
    return type(v).__name__ == "Var"


def _array_invars(eqn):
    return [v for v in eqn.invars if _is_var(v)]


def _region_map(infos: List[EqnInfo]) -> Dict[str, List[EqnInfo]]:
    regions: Dict[str, List[EqnInfo]] = {}
    for info in infos:
        if info.tag is not None:
            regions.setdefault(info.tag.tag, []).append(info)
    return regions


def _region_layer(eqns: List[EqnInfo]) -> str:
    for e in eqns:
        if e.layer:
            return e.layer
    return ""


def _principal_input(walk: _Walk, region: List[EqnInfo]):
    """The largest floating-point tensor a scan/encode region consumes from
    outside itself — the tensor being scanned."""
    region_ids = {id(e.eqn) for e in region}
    best, best_size = None, -1
    for info in region:
        for v in _array_invars(info.eqn):
            cv = walk.canon(v)
            prod = walk.producer.get(cv)
            if prod is not None and id(prod.eqn) in region_ids:
                continue
            aval = v.aval
            if not jnp.issubdtype(aval.dtype, jnp.floating):
                continue
            if aval.size > best_size:
                best, best_size = cv, aval.size
    return best


def _canonical_tensor(walk: _Walk, v):
    """Walk back through content-preserving reshapes/casts so the 'same
    tensor scanned twice' check is insensitive to trivial re-layout."""
    seen = set()
    while True:
        v = walk.canon(v)
        if v in seen:
            return v
        seen.add(v)
        prod = walk.producer.get(v)
        if prod is None:
            return v
        name = prod.eqn.primitive.name
        if name in ("convert_element_type", "reshape", "transpose",
                    "squeeze", "expand_dims", "copy", "stop_gradient"):
            ins = _array_invars(prod.eqn)
            if len(ins) == 1:
                v = ins[0]
                continue
        return v


def _check_rescan(walk: _Walk, regions, workload) -> List[Violation]:
    out = []
    seen: Dict[Any, Tuple[str, str]] = {}
    for tag, eqns in sorted(regions.items()):
        parsed = eqns[0].tag
        if parsed.kind not in ("scan", "encode"):
            continue
        src = _principal_input(walk, eqns)
        if src is None:
            continue
        src = _canonical_tensor(walk, src)
        layer = _region_layer(eqns)
        if src in seen:
            first_tag, first_layer = seen[src]
            out.append(Violation(
                "jaxpr", "RESCAN", layer or first_layer,
                f"tensor scanned twice: {parsed.kind} region {tag} re-scans "
                f"the input of region {first_tag} — derive the mask instead",
                workload))
        else:
            seen[src] = (tag, layer)
    return out


def _check_masks_derived(walk: _Walk, regions, workload) -> List[Violation]:
    out = []
    for tag, eqns in sorted(regions.items()):
        parsed = eqns[0].tag
        if parsed.kind != "gemm":
            continue
        layer = _region_layer(eqns)
        region_ids = {id(e.eqn) for e in eqns}
        # Integer inputs of the dispatch region = masks & queue metadata.
        int_inputs = []
        for info in eqns:
            for v in _array_invars(info.eqn):
                cv = walk.canon(v)
                prod = walk.producer.get(cv)
                if prod is not None and id(prod.eqn) in region_ids:
                    continue
                dt = v.aval.dtype
                if jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_:
                    int_inputs.append(cv)
        for v in dict.fromkeys(int_inputs):
            bad = _trace_mask_origin(walk, v, region_ids)
            if bad is not None:
                out.append(Violation(
                    "jaxpr", "UNDERIVED_MASK", layer,
                    f"mask entering dispatch {tag} originates in "
                    f"untagged op '{bad.eqn.primitive.name}' — sparsity "
                    f"metadata must come from encode/scan/derive/queue",
                    workload))
                break  # one report per dispatch is enough
    return out


def _trace_mask_origin(walk: _Walk, var, consumer_region_ids):
    """None if every path from ``var`` grounds in a sanctioned producer
    region / constant / jaxpr input; else the offending EqnInfo."""
    stack, seen = [var], set()
    while stack:
        v = walk.canon(stack.pop())
        if v in seen:
            continue
        seen.add(v)
        prod = walk.producer.get(v)
        if prod is None:
            continue  # top-level input or constant: provenance unknowable
        if id(prod.eqn) in consumer_region_ids:
            stack.extend(_array_invars(prod.eqn))   # dispatcher plumbing
            continue
        if prod.tag is not None and prod.tag.kind in GROUNDING_KINDS:
            continue                                 # grounded
        if prod.tag is not None and prod.tag.kind == "gemm":
            continue  # another dispatch's (checked) output
        name = prod.eqn.primitive.name
        ins = _array_invars(prod.eqn)
        if not ins:
            continue  # iota etc: index arithmetic, not scanned data
        if name in TRIVIAL_PRIMS or all(
                jnp.issubdtype(i.aval.dtype, jnp.integer)
                or i.aval.dtype == jnp.bool_ for i in ins):
            # Pure bitmap/index arithmetic: keep walking its inputs.
            stack.extend(ins)
            continue
        return prod  # computes int data from float tensors, untagged
    return None


def _check_dense_ops(walk: _Walk, workload,
                     expect_pallas: bool) -> List[Violation]:
    out = []
    for info in walk.infos:
        name = info.eqn.primitive.name
        kind = info.tag.kind if info.tag else None
        if name == "dot_general" and kind != "gemm":
            out.append(Violation(
                "jaxpr", "DENSE_GEMM", info.layer,
                f"dot_general outside any sparse_gemm dispatch region "
                f"(scope: {info.tag.tag if info.tag else '<none>'})",
                workload))
        if name == "conv_general_dilated":
            if kind == "fallback":
                out.append(Violation(
                    "jaxpr", "CONV_FALLBACK", info.layer,
                    "layer escaped the conv engine onto the counted dense "
                    "fallback", workload))
            else:
                out.append(Violation(
                    "jaxpr", "DENSE_CONV", info.layer,
                    "uncounted conv_general_dilated on the traced path",
                    workload))
        if expect_pallas and kind == "gemm" \
                and info.tag.detail.startswith("dense"):
            out.append(Violation(
                "jaxpr", "DENSE_SCHEDULE", info.layer,
                f"dispatch {info.tag.tag} resolved to schedule='dense' "
                f"under a Pallas-audited workload", workload))
    # One DENSE_SCHEDULE region spans many eqns: dedupe by region tag.
    deduped, seen = [], set()
    for v in out:
        key = (v.code, v.message)
        if key in seen:
            continue
        seen.add(key)
        deduped.append(v)
    return deduped


def _check_collectives(walk: _Walk, workload) -> List[Violation]:
    """Every cross-shard data movement must sit in a collective region:
    an untagged psum is gradient traffic that bypassed the bitmap-aware
    all-reduce (and with it the compression, the stats keys, and the
    fault-injection tap)."""
    out, seen = [], set()
    for info in walk.infos:
        if info.eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        if info.tag is not None and info.tag.kind == "collective":
            continue
        key = (info.layer, info.eqn.primitive.name,
               info.tag.tag if info.tag else "")
        if key in seen:
            continue
        seen.add(key)
        out.append(Violation(
            "jaxpr", "COLLECTIVE_UNTAGGED", info.layer,
            f"'{info.eqn.primitive.name}' outside any repro:collective "
            f"region (scope: {info.tag.tag if info.tag else '<none>'}) — "
            f"cross-shard traffic must go through sharding/collectives",
            workload))
    return out


def audit_jaxpr(closed_jaxpr, *, workload: str = "",
                expect_pallas: bool = True) -> List[Violation]:
    """Run every jaxpr-level check on an already-traced program."""
    walk = _Walk(closed_jaxpr)
    regions = _region_map(walk.infos)
    out: List[Violation] = []
    out += _check_rescan(walk, regions, workload)
    out += _check_masks_derived(walk, regions, workload)
    out += _check_dense_ops(walk, workload, expect_pallas)
    out += _check_collectives(walk, workload)
    return out


def audit_fn(fn, *args, workload: str = "",
             expect_pallas: bool = True) -> List[Violation]:
    """Trace ``fn(*args)`` (no execution) and audit the result, including
    the trace-time GemmSpec provenance check."""
    from repro.kernels import ops

    with ops.collect_gemm_events() as events:
        closed = jax.make_jaxpr(fn)(*args)
    out = audit_jaxpr(closed, workload=workload, expect_pallas=expect_pallas)
    for spec in events:
        if spec.origin != "policy":
            out.append(Violation(
                "jaxpr", "SPEC_UNRESOLVED", "",
                f"sparse_gemm dispatched with an ad-hoc GemmSpec "
                f"(origin={spec.origin!r}, schedule={spec.schedule!r}); "
                f"resolve specs through SparsityPolicy.gemm_spec",
                workload))
    return out


# ---------------------------------------------------------------------------
# Standard audited workloads — the zero-violation gate on main
# ---------------------------------------------------------------------------

def _audit_policy():
    from repro.core import policy as pol
    return pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))


def _cnn_step(name: str, *, image_size: int, width: float, batch: int = 2):
    from repro.models.cnn import build_cnn
    model = build_cnn(name, image_size=image_size, width=width,
                      num_classes=10)
    params = model.init(jax.random.PRNGKey(0))
    images = jnp.zeros((batch, image_size, image_size, 3), jnp.float32)
    labels = jnp.zeros((batch,), jnp.int32)
    policy = _audit_policy()

    def step(p):
        return model.loss(p, images, labels, policy)

    return (lambda: jax.grad(step)(params))


def _ffn_step(batch: int = 4):
    from repro.models.ffn import FFNConfig, ffn_apply, ffn_init
    cfg = FFNConfig(d_model=16, d_ff=32, activation="relu",
                    sparse_policy=_audit_policy())
    params = ffn_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((batch, cfg.d_model), jnp.float32)

    def step(p):
        return (ffn_apply(p, x, cfg) ** 2).sum()

    return (lambda: jax.grad(step)(params))


def _spmd_mesh():
    """A 1-device mesh: the audit only TRACES, and a shard_map body's
    jaxpr is mesh-size-independent — so the contract verified here holds
    for any device count (the 8-device execution tests live in
    tests/test_sparse_collectives.py)."""
    return jax.make_mesh((1,), ("data",))


def _ffn_spmd_step(batch: int = 4):
    from repro.models.ffn import FFNConfig, ffn_apply, ffn_init
    from repro.sharding import spmd_step
    cfg = FFNConfig(d_model=16, d_ff=32, activation="relu",
                    sparse_policy=_audit_policy())
    params = ffn_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((batch, cfg.d_model), jnp.float32)

    def loss_fn(p, xb):
        return (ffn_apply(p, xb, cfg) ** 2).sum()

    f = spmd_step.make_spmd_grad_fn(loss_fn, _spmd_mesh())
    return (lambda: f(params, x))


def _cnn_spmd_step(name: str, *, image_size: int, width: float,
                   batch: int = 2):
    from repro.models.cnn import build_cnn
    from repro.sharding import spmd_step
    model = build_cnn(name, image_size=image_size, width=width,
                      num_classes=10)
    params = model.init(jax.random.PRNGKey(0))
    images = jnp.zeros((batch, image_size, image_size, 3), jnp.float32)
    labels = jnp.zeros((batch,), jnp.int32)
    policy = _audit_policy()

    def loss_fn(p, b):
        return model.loss(p, b["images"], b["labels"], policy)

    f = spmd_step.make_spmd_grad_fn(loss_fn, _spmd_mesh())
    return (lambda: f(params, {"images": images, "labels": labels}))


WORKLOADS = {
    # VGG16: the deep sequential CNN (dense convs at every depth).
    "vgg16": lambda: _cnn_step("vgg16", image_size=16, width=0.125),
    # MobileNet: the depthwise/pointwise stack — exercises the grouped
    # engine with degenerate K = R·S tiles end to end.
    "mobilenet": lambda: _cnn_step("mobilenet", image_size=16, width=0.25),
    # ReLU-FFN: the linear-layer fused unit (act_matmul/matmul path).
    "ffn_relu": lambda: _ffn_step(),
    # SPMD variants: the same units inside a shard_map body with the
    # bitmap-compressed gradient all-reduce — the lifecycle contracts plus
    # COLLECTIVE_UNTAGGED, verified through the shard_map descent.
    "ffn_relu_spmd": lambda: _ffn_spmd_step(),
    "vgg16_spmd": lambda: _cnn_spmd_step("vgg16", image_size=16,
                                         width=0.125),
}


def audit_workloads(names=None) -> List[Violation]:
    """Trace-and-audit the standard workloads; [] is the contract on main."""
    out: List[Violation] = []
    for name in (names or sorted(WORKLOADS)):
        thunk = WORKLOADS[name]()
        out += audit_fn(thunk, workload=name)
    return out
