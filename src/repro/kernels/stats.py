"""Bitmap-op instrumentation: how many times per step is sparsity metadata
*computed* (a dense scan / fused encode over tensor-sized data), as opposed
to *derived* (coarsen / transpose / im2col on an existing bitmap)?

The paper's Encoder produces each layer's sparsity metadata exactly once per
pass and amortizes it over O(M·k²) reuse (§4.1).  The seed code instead
re-scanned activations up to three times per layer per step.  This counter
makes the difference auditable: ``benchmarks/kernel_audit.bitmap_op_audit``
asserts exactly ONE computation per activation per training step.

Counts are recorded at Python trace time, so one eager fwd+bwd (or one
trace of a jitted step) yields the per-step op count.  Derivations are
deliberately NOT recorded — they are pure bitmap arithmetic, the cheap
"free byproduct" reuse the paper is about.

Key families (normalized):
  encode:act / scan:<what> / scan_pallas:<what>   bitmap computations
  queue:<builder>                                 work-queue constructions
  gemm:<schedule>:<g>                             one per sparse_gemm
                                                  dispatch (schedule ∈
                                                  {predicated, compact,
                                                  dense}; g = group count)
  pad_operand:<a|b|mult>                          a tiled dispatch that had
                                                  to pad that operand up to
                                                  whole tiles (a copy of it)
  conv:dense_fallback                             escaped-the-engine convs
  fallback:queue_overflow                         compact dispatches whose
                                                  live count exceeded the
                                                  queue capacity (concrete
                                                  dispatches only — traced
                                                  ones can't be counted)
  registry:hit / registry:miss                    grad-bitmap registry
                                                  lookups (a miss means a
                                                  consumer proceeds with no
                                                  dy mask — lost skipping,
                                                  never wrong numerics)
  guard:<event>                                   runtime guard layer
                                                  (docs/resilience.md):
                                                  nonfinite_skip,
                                                  bitmap_mismatch,
                                                  registry_miss, demote,
                                                  quarantine_clamp,
                                                  ckpt_fallback,
                                                  verdict:<v>
"""
from __future__ import annotations

import collections
import itertools
from typing import Dict, Optional, Tuple

import jax

_COUNTS: "collections.Counter[str]" = collections.Counter()


def record(kind: str) -> None:
    """Register one counted event.  ``kind`` is ``<how>:<what>``:
    how ∈ {encode, scan, scan_pallas, queue, gemm} (fused-kernel vs
    standalone dense scan vs work-queue construction vs GEMM dispatch),
    what ∈ {act, grad} for encode/scan; for queue it is the builder backend
    ∈ {prefix_sum, argsort} — so ``queue_builds("argsort")`` audits that the
    default compact path never sorts (the PR-2 contract); for gemm it is
    ``<schedule>:<g>`` — the dispatcher's normalized launch key."""
    _COUNTS[kind] += 1


def reset() -> None:
    _COUNTS.clear()
    _LIVE.clear()


def counts() -> Dict[str, int]:
    return dict(_COUNTS)


def total(what: str = "") -> int:
    """Total computations, optionally filtered by the ``:<what>`` suffix."""
    return sum(v for k, v in _COUNTS.items()
               if not what or k.endswith(":" + what))


def queue_builds(builder: str = "") -> int:
    """Work-queue constructions, optionally for one builder backend.
    ``queue_builds("argsort") == 0`` is the no-sort-on-the-critical-path
    assertion for the default compact schedule."""
    return sum(v for k, v in _COUNTS.items()
               if k.startswith("queue:")
               and (not builder or k == "queue:" + builder))


def gemm_launches(schedule: str = "", groups: Optional[int] = None) -> int:
    """GEMM dispatches (``gemm:<schedule>:<g>``), optionally filtered by
    schedule and/or group count — the reader the kernel audits use for the
    normalized per-launch keys."""
    n = 0
    for k, v in _COUNTS.items():
        if not k.startswith("gemm:"):
            continue
        # A key without the :<g> suffix has an unknown group field; the
        # group filter skips it rather than crashing the reader.
        _, _, tail = k.partition(":")
        sched, _, g = tail.partition(":")
        if schedule and sched != schedule:
            continue
        if groups is not None and (not g.isdigit() or int(g) != groups):
            continue
        n += v
    return n


def guard_counts() -> Dict[str, int]:
    """The ``guard:*`` family — the runtime guard layer's detection and
    verdict counters (docs/resilience.md)."""
    return {k: v for k, v in _COUNTS.items() if k.startswith("guard:")}


# Runtime (execution-time) counters ride on jax.debug.callback — a host
# round-trip per execution PER SHARD.  That is the right trade for audits
# and tests, but on a hot path being wall-clock benchmarked the callbacks
# dominate the thing measured; this trace-time switch lets a harness trace
# without them.  Default ON: correctness tooling never has to opt in.
_RUNTIME_COUNTING = True


def set_runtime_counting(on: bool) -> bool:
    """Enable/disable ``record_at_runtime`` callback staging at trace time;
    returns the previous setting (restore it in a finally)."""
    global _RUNTIME_COUNTING
    prev, _RUNTIME_COUNTING = _RUNTIME_COUNTING, bool(on)
    return prev


def record_at_runtime(kind: str, flag) -> None:
    """Increment counter ``kind`` at EXECUTION time by the runtime value of
    ``flag`` (a traced 0/1 scalar) — the escape hatch for events that only
    exist at run time, like the optimizer's non-finite skip.  ``record``
    counts at trace time (once per trace); this counts once per execution
    in which ``flag`` is nonzero, via an async host callback (it does not
    force a device sync on the value's consumers).  A no-op while
    ``set_runtime_counting(False)`` is in effect (benchmark harnesses)."""
    if not _RUNTIME_COUNTING:
        return
    import jax as _jax

    def _cb(v):
        if float(v) != 0.0:
            _COUNTS[kind] += 1

    _jax.debug.callback(_cb, flag)


# ---------------------------------------------------------------------------
# Live-tile telemetry — the measured signal the spec-keyed autotuner tunes on
# ---------------------------------------------------------------------------
#
# The counters above say WHICH kernels launched; these buffers say how much
# of each launch was live.  Every concrete (non-traced) ``sparse_gemm``
# dispatch records its unpadded live-tile fractions under its autotune key
# (``kernels/autotune.key_for``): the fraction of live OUTPUT tiles (the
# compact queue's work units) and the min live fraction across operand
# masks (the input-skipping signal).  A bounded ring buffer per key keeps
# the trailing window of recent steps — what ``AutotuneCache.resolve``
# reads to pick a schedule, and what its drift re-evaluation compares
# against.  Traced dispatches carry tracers and record nothing: these are
# MEASURED fractions, never modeled ones.

LIVE_WINDOW = 128

_LIVE: Dict[str, "collections.deque[Tuple[float, float]]"] = {}


def record_live_tiles(key: str, out_frac: float,
                      operand_frac: float = 1.0) -> None:
    """Append one measured (out, operand) live-tile fraction pair for
    ``key`` (bounded: the newest ``LIVE_WINDOW`` samples are kept)."""
    buf = _LIVE.get(key)
    if buf is None:
        buf = _LIVE[key] = collections.deque(maxlen=LIVE_WINDOW)
    buf.append((float(out_frac), float(operand_frac)))


def live_tile_stats(key: str, window: Optional[int] = None
                    ) -> Tuple[Optional[float], Optional[float], int]:
    """(mean out-live fraction, mean operand-live fraction, n) over the
    trailing ``window`` samples for ``key`` — (None, None, 0) if nothing
    has been observed."""
    buf = _LIVE.get(key)
    if not buf:
        return None, None, 0
    items = list(buf)
    if window is not None:
        items = items[-window:]
    outs = sum(o for o, _ in items) / len(items)
    opnds = sum(p for _, p in items) / len(items)
    return outs, opnds, len(items)


def live_tile_keys() -> list:
    """Keys that have at least one recorded live-tile sample."""
    return [k for k, v in _LIVE.items() if v]


# ---------------------------------------------------------------------------
# Scopes — the static (jaxpr-visible) twin of the counters above
# ---------------------------------------------------------------------------
#
# Counters audit a trace that RAN.  ``analysis/jaxpr_audit.py`` proves the
# same lifecycle contract on any program WITHOUT running it, by walking the
# jaxpr's ``eqn.source_info.name_stack``.  For that, every bitmap event must
# leave a machine-readable tag in the traced program, which ``jax.named_scope``
# provides: scope names survive tracing, jvp and transposition (they reappear
# wrapped as ``jvp(tag)`` / ``transpose(jvp(tag))``).
#
# Tag grammar (parsed by analysis.jaxpr_audit.parse_tag):
#
#     repro:<kind>[:<detail>]:<seq>
#
# Lifecycle kinds ∈ {encode, scan, derive, queue, gemm, fallback,
# collective} — mirroring the counter families.  <seq> is a process-global
# instance number so two scans of the SAME tensor get DISTINCT region
# identities (that duplication is exactly the violation the audit must be
# able to see).
#
# Attribution kinds name where the rest of a step's device time goes; they
# are not bitmap events, and the audit looks through them:
#
#     layout:<fp|bp|wg>          the conv engine's data-side operand and
#                                result re-layout per stage (im2col, dy
#                                dilation, weight flip/transpose, σ′
#                                multiplier, pm.T, group and output
#                                reshapes); never encloses a lifecycle scope
#     layout:lift                ``sparse_gemm``'s lift of 2-D operands to
#                                the grouped engine's G=1, and the unlift
#                                of its result, outside the gemm scope
#     pool:<max|avg|gap>         a pool and the ReLU applied just before it
#     norm:bn                    BatchNorm
#     merge:<add|concat>         a branch's merge and the ReLU on its entry
#     pad:<schedule>             inside a gemm scope: operand, mask and
#                                epilogue-multiplier padding
#     scatter:<schedule>         inside a gemm scope: the compact queue's
#                                scatter back to dense tiles, and the unpad
#
# Scope names reach the compiled HLO's ``op_name`` metadata, where the chip
# benchmark's trace reduction reads them (benchmarks/chip/metrics/): XLA ops
# that autodiff derives from a scope keep it as ``transpose(jvp(tag))``.
# Attribution tags draw <seq> from a count of their own, so adding them
# leaves every lifecycle tag — and the Pallas kernels named after one —
# as it was.  Model layers use the separate ``layer:<name>`` grammar
# (``layer_scope``) for keying violation reports by layer.

LIFECYCLE_KINDS = ("encode", "scan", "derive", "queue", "gemm", "fallback",
                   "collective")
ATTRIBUTION_KINDS = ("layout", "pool", "norm", "merge", "pad", "scatter")

_SCOPE_SEQ = itertools.count()
_ATTRIBUTION_SEQ = itertools.count()


def lifecycle_scope(kind: str, detail: str = ""):
    """A ``jax.named_scope`` carrying one ``repro:`` tag.

    Lifecycle kinds wrap the ops that *compute* sparsity metadata
    (kind="encode"/"scan"), *derive* it (kind="derive"), build work queues
    (kind="queue"), consume it in a GEMM dispatch (kind="gemm"), escape the
    engine entirely (kind="fallback") or move it across shards
    (kind="collective").  Attribution kinds (``ATTRIBUTION_KINDS``) wrap
    the device work around them.  ``detail`` refines the kind (e.g. the
    scan target, the gemm ``<schedule>:<g>`` launch key, the conv stage).
    """
    if kind in LIFECYCLE_KINDS:
        seq = next(_SCOPE_SEQ)
    elif kind in ATTRIBUTION_KINDS:
        seq = next(_ATTRIBUTION_SEQ)
    else:
        raise ValueError(f"unknown scope kind {kind!r}")
    parts = ["repro", kind] + ([detail] if detail else []) + [str(seq)]
    return jax.named_scope(":".join(parts))


def layer_scope(name: str):
    """A ``jax.named_scope`` keying everything under it to one model layer —
    the audit uses it only to label violations (``layer:<name>``)."""
    return jax.named_scope(f"layer:{name}")
