"""Public, jit-friendly wrappers around the Pallas kernels.

THE masked-GEMM entry point is ``sparse_gemm(a, b, masks, spec)``:

  * ``GemmSpec`` is a frozen, hashable request object — tile shape, group
    count, schedule ∈ {predicated, compact, dense}, a composable tuple of
    epilogue stages ⊆ {sigma_prime, bitmap_emit}, queue builder, queue
    capacity, output dtype.  It is static metadata: shardable, cacheable,
    and printable, where the old API threaded seven loose kwargs through
    every layer.
  * ``GemmMasks`` carries the (out, a, b) block bitmaps; ``None`` on any
    slot means dense on that axis pair.
  * The dispatcher owns the pad / queue / overflow-fallback / scatter
    contract in EXACTLY ONE place: 2-D operands are lowered as the G=1
    special case of the grouped engine, so every GEMM in the system —
    linear, conv im2col, grouped/depthwise, WG — shares one tuned
    implementation (the SparseTrain/TensorDash "single uniform sparse
    dataflow" lesson).

Handles:
  * automatic interpret-mode selection (CPU backend → interpret=True, so the
    whole framework is testable on a CPU host while targeting TPU; every
    other backend compiles the kernels),
  * block-alignment padding (MXU-aligned defaults bm=bk=bn=128; padded
    blocks are marked inactive so they are skipped, not computed),
  * the compact (work-redistribution) launch path, including the active-
    coordinate queue construction and the scatter back to dense layout,
  * a ``schedule="dense"`` lowering (dense compute + output masking) that
    is numerically identical to the kernels — the xla_ref policy path.

With the ``bitmap_emit`` epilogue stage, a dispatch also returns the
packed any-nonzero bitmap of its own output — emitted at accumulator
writeback, so backward-pass metadata (the dy bitmap) is a free byproduct
of the GEMM that produced the dy, exactly as ``relu_encode`` makes the
activation bitmap a byproduct of the forward ReLU.  Every dispatch is
counted by ``kernels.stats`` under ``gemm:<schedule>:<g>`` (plus
``emit:grad`` per emitted bitmap).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import ref, stats
from .bitmap_scan import bitmap_scan_kernel
from .masked_matmul import (
    grouped_compact_masked_matmul_kernel,
    grouped_masked_matmul_kernel,
)
from .queue_builder import build_queue
from .relu_encode import relu_encode_kernel
from .shapes import (
    block_bitmap, ceil_to, grid_shape, pad3, pad_mask3, pad_to, slab_rows,
)

# MXU-native tile. Tests sweep smaller tiles in interpret mode.
DEFAULT_BLOCK = (128, 128, 128)

SCHEDULES = ("predicated", "compact", "dense")
# Composable epilogue stages, in canonical application order: the σ′
# Hadamard first, then bitmap emission over the POST-σ′ values (the
# emitted bits must describe exactly what is written back).
EPILOGUE_STAGES = ("sigma_prime", "bitmap_emit")


def normalize_epilogue(epilogue) -> Tuple[str, ...]:
    """Canonicalize an epilogue declaration to a stage tuple.

    Accepts the legacy strings (``"none"``/``"sigma_prime"``), ``None``,
    or any iterable of stage names; returns the stages in canonical order
    with duplicates rejected."""
    if epilogue is None or epilogue == "none" or epilogue == ():
        return ()
    stages = (epilogue,) if isinstance(epilogue, str) else tuple(epilogue)
    bad = [s for s in stages if s not in EPILOGUE_STAGES]
    if bad or len(set(stages)) != len(stages):
        raise ValueError(
            f"epilogue stages must be unique and drawn from "
            f"{EPILOGUE_STAGES}, got {epilogue!r}")
    return tuple(s for s in EPILOGUE_STAGES if s in stages)


def _use_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode only where asked, or automatically on the CPU backend
    (where tests run); any other backend compiles the kernels, so a run
    that meant to use the chip fails instead of quietly interpreting."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# The request objects
# ---------------------------------------------------------------------------

class GemmMasks(NamedTuple):
    """Block bitmaps for one GEMM; ``None`` ⇒ dense on that axis pair.

    2-D request (G=1): out (Mb, Nb), a (Mb, Kb), b (Kb, Nb).
    Grouped request:   each mask carries a leading G axis.
    """
    out: Optional[jnp.ndarray] = None
    a: Optional[jnp.ndarray] = None
    b: Optional[jnp.ndarray] = None


@dataclasses.dataclass(frozen=True)
class GemmSpec:
    """One masked GEMM, fully described as static metadata.

    schedule:
      * "predicated" — full (G, Mb, Nb, Kb) grid; each step guards its MXU
        issue on the masks (the paper's baseline sparse PE).
      * "compact"    — work-redistribution: ONE queue of active (g, i, j)
        tiles spanning all groups (lexicographic WDU order), built by
        ``queue_builder``; overflow beyond ``max_active_blocks`` falls back
        to the predicated schedule at runtime — never a silent truncation.
      * "dense"      — no Pallas launch: dense compute + output-mask +
        epilogue, numerically identical (the xla_ref policy path; operand
        masks are accounted by the cost model, not consumed).

    epilogue: a tuple of composable stages (normalized from the legacy
    strings ``"none"``/``"sigma_prime"``), applied at accumulator
    writeback in canonical order:
      * ``"sigma_prime"`` — Hadamard with an (M, N) multiplier (the
        backward σ′ multiply).  The multiplier itself is DATA and is
        passed to ``sparse_gemm(..., epilogue_mult=)``; the spec only
        declares the shape of the launch, so it stays hashable/static.
      * ``"bitmap_emit"`` — reduce the written (post-σ′) values to their
        (``emit_gran``) any-nonzero bitmap in the same writeback, so the
        producing GEMM hands its consumer the mask for free (no separate
        ``bitmap_scan`` pass).  ``sparse_gemm`` then returns
        ``(out, bitmap)``.

    emit_gran: the (er, ec) bitmap granularity, required iff
    ``"bitmap_emit"`` is staged; must divide the (bm, bn) tile edges.

    max_active_blocks: compact-queue capacity (None → all tiles, which
    provably cannot overflow).  interpret: None → auto (CPU ⇒ True).

    origin records WHO resolved the spec — ``"policy"`` when it came out of
    ``SparsityPolicy.gemm_spec()`` (the one sanctioned resolution point),
    ``"adhoc"`` otherwise.  It is provenance metadata for the static
    analyzer's SPEC_UNRESOLVED check, deliberately excluded from eq/hash so
    a policy-resolved spec and its ad-hoc twin stay interchangeable as jit
    cache keys.
    """
    block: Tuple[int, int, int] = DEFAULT_BLOCK
    groups: int = 1
    schedule: str = "predicated"
    epilogue: Tuple[str, ...] = ()
    emit_gran: Optional[Tuple[int, int]] = None
    queue_builder: str = "prefix_sum"
    max_active_blocks: Optional[int] = None
    out_dtype: Any = jnp.float32
    interpret: Optional[bool] = None
    origin: str = dataclasses.field(default="adhoc", compare=False)

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {self.schedule!r}")
        object.__setattr__(self, "epilogue",
                           normalize_epilogue(self.epilogue))
        if self.groups < 1:
            raise ValueError(f"groups must be >= 1, got {self.groups}")
        if len(self.block) != 3 or any(e < 1 for e in self.block):
            raise ValueError(f"block must be 3 positive edges: {self.block}")
        if self.emits_bitmap:
            bm, _, bn = self.block
            if (self.emit_gran is None or len(self.emit_gran) != 2
                    or bm % self.emit_gran[0] or bn % self.emit_gran[1]):
                raise ValueError(
                    f"bitmap_emit epilogue requires emit_gran dividing "
                    f"(bm, bn)={bm, bn}, got {self.emit_gran!r}")
        elif self.emit_gran is not None:
            raise ValueError(
                f"emit_gran={self.emit_gran!r} without a bitmap_emit "
                f"epilogue stage")

    def with_(self, **kw) -> "GemmSpec":
        return dataclasses.replace(self, **kw)

    @property
    def fuses_mult(self) -> bool:
        """Whether the ``sigma_prime`` Hadamard stage is declared."""
        return "sigma_prime" in self.epilogue

    @property
    def emits_bitmap(self) -> bool:
        """Whether the ``bitmap_emit`` stage is declared (dispatch then
        returns ``(out, bitmap)``)."""
        return "bitmap_emit" in self.epilogue

    @property
    def stats_key(self) -> str:
        """The normalized per-launch counter key: ``gemm:<schedule>:<g>``."""
        return f"gemm:{self.schedule}:{self.groups}"

    def launch_geometry(self, m: int, k: int, n: int) -> dict:
        """Static launch geometry this spec resolves to for per-group dims
        (M, K, N) — the single source of truth the dispatcher pads/launches
        by, and what ``benchmarks/kernel_audit.launch_shape_audit`` pins so
        future spec changes can't silently regress launch shapes."""
        bm, bk, bn = self.block
        ni, nk, nj = grid_shape((m, k, n), self.block)
        g = self.groups
        geom = {
            "schedule": self.schedule,
            "groups": g,
            "block": (bm, bk, bn),
            "padded": (g, ni * bm, nk * bk, nj * bn),
            "queue_capacity": 0,
            "grid": (),
        }
        if self.schedule == "dense":
            return geom
        predicated_grid = (g, ni, nj, nk)
        if self.schedule == "compact":
            cap = self.max_active_blocks
            geom["queue_capacity"] = g * ni * nj if cap is None else cap
            geom["grid"] = (geom["queue_capacity"], nk)
            geom["fallback_grid"] = predicated_grid
        else:
            geom["grid"] = predicated_grid
        return geom


MasksLike = Union[GemmMasks, Sequence[Optional[jnp.ndarray]], None]


def _as_masks(masks: MasksLike) -> GemmMasks:
    if masks is None:
        return GemmMasks()
    if isinstance(masks, GemmMasks):
        return masks
    return GemmMasks(*masks)


# ---------------------------------------------------------------------------
# The dispatcher — the ONE pad/queue/overflow-fallback/scatter implementation
# ---------------------------------------------------------------------------

# Trace-time dispatch events for the static analyzer's SPEC_UNRESOLVED
# check: while a ``collect_gemm_events()`` context is active, every
# ``sparse_gemm`` dispatch appends its spec here.  Tracing is single-
# threaded per process, so a plain module slot (not a contextvar) is enough.
_GEMM_EVENTS: Optional[List[GemmSpec]] = None

# Fault-injection tap (repro/runtime/faults.py): when a hook is installed,
# every dispatch offers named values for tampering — the chaos harness uses
# it to shrink a compact queue's capacity ("gemm:spec") or flip bits in an
# emitted bitmap ("gemm:emit_bits") without the kernels layer importing the
# runtime layer.  None (the default) is a zero-cost passthrough.
_TAMPER_HOOK = None


def set_tamper_hook(fn):
    """Install (or, with None, remove) the fault-injection tamper hook;
    returns the previous hook so callers can restore it."""
    global _TAMPER_HOOK
    prev, _TAMPER_HOOK = _TAMPER_HOOK, fn
    return prev


def _tamper(site: str, value):
    return value if _TAMPER_HOOK is None else _TAMPER_HOOK(site, value)


@contextlib.contextmanager
def collect_gemm_events():
    """Record every ``sparse_gemm`` dispatch (its ``GemmSpec``) traced or
    executed inside the context — the audit traces a model step under this
    and then asserts each spec's provenance (``origin == "policy"``)."""
    global _GEMM_EVENTS
    prev, _GEMM_EVENTS = _GEMM_EVENTS, []
    try:
        yield _GEMM_EVENTS
    finally:
        _GEMM_EVENTS = prev


def sparse_gemm(
    a: jnp.ndarray,
    b: jnp.ndarray,
    masks: MasksLike = None,
    spec: Optional[GemmSpec] = None,
    *,
    epilogue_mult: Optional[jnp.ndarray] = None,
):
    """Block-sparse GEMM with output/input sparsity skipping — the single
    entry point for every masked GEMM in the system.

    2-D request: ``a`` (M, K) @ ``b`` (K, N) with ``spec.groups == 1`` —
    lowered as the G=1 special case of the grouped engine.
    Grouped request: ``a`` (G, M, K) @ ``b`` (G, K, N) batched per group
    (``spec.groups == G``); masks carry a leading G axis and groups never
    mix (the group-boundary contract).

    Result equals the dense product masked by ``expand(masks.out)`` (and
    Hadamard-multiplied by ``epilogue_mult`` when the spec stages
    ``sigma_prime``) exactly — skipping is lossless by construction.

    With the ``bitmap_emit`` stage, returns ``(out, bitmap)`` where
    ``bitmap`` is the packed (⌈M/er⌉, ⌈N/ec⌉) int32 any-nonzero bitmap of
    the returned (post-epilogue) values at ``spec.emit_gran`` — emitted at
    accumulator writeback, identical to a fresh ``bitmap_scan`` of the
    output, and counted as ``emit:grad`` (a bitmap computation, not a
    rescan).
    """
    spec = GemmSpec() if spec is None else spec
    spec = _tamper("gemm:spec", spec)
    masks = _as_masks(masks)
    if (epilogue_mult is not None) != spec.fuses_mult:
        raise ValueError(
            f"spec.epilogue={spec.epilogue!r} but epilogue_mult "
            f"{'is' if epilogue_mult is not None else 'is not'} provided")
    grouped_in = a.ndim == 3
    if not grouped_in:
        if spec.groups != 1:
            raise ValueError(
                f"2-D operands require spec.groups == 1, got {spec.groups}")
        with stats.lifecycle_scope("layout", "lift"):
            a3, b3 = a[None], b[None]
            masks = GemmMasks(*(m if m is None else m[None]
                                for m in masks))
            mult3 = None if epilogue_mult is None else epilogue_mult[None]
    else:
        if a.shape[0] != spec.groups:
            raise ValueError(
                f"operand group axis {a.shape[0]} != spec.groups "
                f"{spec.groups}")
        a3, b3, mult3 = a, b, epilogue_mult
    stats.record(spec.stats_key)
    if spec.emits_bitmap:
        # The emitted bitmap is a gradient-side bitmap COMPUTATION (it
        # replaces the standalone scan_pallas:grad pass), so it counts
        # toward the one-computation-per-tensor-per-step budget.
        stats.record("emit:grad")
    if _GEMM_EVENTS is not None:
        _GEMM_EVENTS.append(spec)
    _observe_live_tiles(spec, a3, b3, masks)
    with stats.lifecycle_scope("gemm", f"{spec.schedule}:{spec.groups}"):
        res = _dispatch(a3, b3, masks, spec, mult3)
    if spec.emits_bitmap:
        out, bits = res
        res = (out, _tamper("gemm:emit_bits", bits))
    if grouped_in:
        return res
    with stats.lifecycle_scope("layout", "lift"):
        return jax.tree.map(lambda x: x[0], res)


def _observe_live_tiles(spec: GemmSpec, a3, b3, masks: GemmMasks) -> None:
    """Measured live-tile telemetry for the autotuner (kernels/autotune.py).

    Only CONCRETE masks are observed — an eager dispatch (the wall-clock
    harness, probe steps, eager grads' forward pass) yields real measured
    fractions; a traced dispatch carries tracers and records nothing, so
    the telemetry is never a modeled number.  Fractions are over the
    UNPADDED block bitmaps: the fraction of live output tiles (the compact
    queue's work units; 1.0 when no out mask) and the min live fraction
    across operand masks (the input-skipping signal)."""
    present = [m for m in masks if m is not None]
    if not present or any(isinstance(m, jax.core.Tracer) for m in present):
        return
    import numpy as np

    def frac(m) -> float:
        arr = np.asarray(m)
        return float(arr.astype(bool).mean()) if arr.size else 1.0

    out_frac = frac(masks.out) if masks.out is not None else 1.0
    operand = [frac(m) for m in (masks.a, masks.b) if m is not None]
    op_frac = min(operand) if operand else 1.0
    from . import autotune
    _, m, k = a3.shape
    autotune.observe_dispatch(spec, (m, k, b3.shape[2]), out_frac, op_frac)


def _dispatch(a, b, masks: GemmMasks, spec: GemmSpec, mult):
    """Pad → (queue →) launch → (scatter →) unpad.  Exists exactly once.

    Returns ``out`` (G, M, N) — or ``(out, bits)`` with the emitted
    (G, ⌈M/er⌉, ⌈N/ec⌉) bitmap when the spec stages ``bitmap_emit``.
    Every branch (dense, predicated, compact, overflow fallback) produces
    the same pytree structure, so the runtime ``lax.cond`` composes."""
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 == spec.groups and k == k2, (a.shape, b.shape, spec)
    bm, bk, bn = spec.block
    out_dtype = spec.out_dtype
    emit = spec.emit_gran if spec.emits_bitmap else None
    if mult is not None:
        assert mult.shape == (g, m, n), (mult.shape, (g, m, n))

    if spec.schedule == "dense":
        # Numerically-equivalent dense compute + masking: the skipped work
        # is accounted by core.costmodel, not saved on this backend.
        # Operand masks are metadata-only here (they feed the cost model).
        out = jnp.einsum("gmk,gkn->gmn", a.astype(jnp.float32),
                         b.astype(jnp.float32))
        if masks.out is not None:
            em = jax.vmap(lambda mk: ref.expand_block_mask(mk, bm, bn))(
                masks.out.astype(jnp.float32))
            out = out * em[:, :m, :n]
        if mult is not None:
            out = out * mult.astype(jnp.float32)
        if emit is None:
            return out.astype(out_dtype)
        er, ec = emit
        me, ne = ceil_to(m, er), ceil_to(n, ec)
        ob = jnp.abs(pad3(out, me, ne))
        bits = (jnp.max(ob.reshape(g, me // er, er, ne // ec, ec),
                        axis=(2, 4)) > 0).astype(jnp.int32)
        return out.astype(out_dtype), bits

    ni, nk, nj = grid_shape((m, k, n), spec.block)
    mp, kp, np_ = ni * bm, nk * bk, nj * bn
    for name, x, tiled in (("a", a, (mp, kp)), ("b", b, (kp, np_)),
                           ("mult", mult, (mp, np_))):
        if x is not None and x.shape[1:] != tiled:
            stats.record(f"pad_operand:{name}")
    with stats.lifecycle_scope("pad", spec.schedule):
        a_p = pad3(a, mp, kp)
        b_p = pad3(b, kp, np_)
        mult_p = None if mult is None \
            else pad3(mult.astype(jnp.float32), mp, np_)
        om = pad_mask3(masks.out, g, ni, nj)
        am = pad_mask3(masks.a, g, ni, nk)
        bmask = pad_mask3(masks.b, g, nk, nj)
    itp = _use_interpret(spec.interpret)

    def _predicated():
        return grouped_masked_matmul_kernel(
            a_p, b_p, om, am, bmask,
            bm=bm, bk=bk, bn=bn, out_dtype=out_dtype,
            epilogue_mult=mult_p, emit_gran=emit, interpret=itp,
        )

    if spec.schedule == "compact":
        s_cap = spec.max_active_blocks \
            if spec.max_active_blocks is not None else g * ni * nj
        # One queue over all groups: flatten (G, Mb, Nb) to (G·Mb, Nb) so
        # the row-major builder order IS lexicographic (g, i, j) — the WDU
        # dispatch order lifted to the group axis; decode the group
        # coordinate back out of the fused row index.
        fi, jj, n_live_v = build_queue(
            om.reshape(g * ni, nj), capacity=s_cap,
            builder=spec.queue_builder)
        gg = fi // ni
        ii = fi % ni
        n_live = n_live_v[0]
        n_active = jnp.minimum(n_live, s_cap).reshape(1)

        def _compact():
            compacted = grouped_compact_masked_matmul_kernel(
                a_p, b_p, gg, ii, jj, n_active, am, bmask,
                bm=bm, bk=bk, bn=bn, out_dtype=out_dtype,
                epilogue_mult=mult_p, emit_gran=emit, interpret=itp,
            )
            if emit is not None:
                compacted, bits_c = compacted
            with stats.lifecycle_scope("scatter", spec.schedule):
                # Scatter the queue back to dense tile layout.  Padding steps
                # carry zero tiles at coords of dead queue slots — we direct
                # dead slots at (0, 0, 0) via scatter-ADD so they are no-ops.
                live_slot = jnp.arange(s_cap) < n_active[0]
                live = live_slot.astype(out_dtype)
                masked = compacted * live[:, None, None]
                sg = jnp.where(live_slot, gg, 0)
                si = jnp.where(live_slot, ii, 0)
                sj = jnp.where(live_slot, jj, 0)
                out_tiles = jnp.zeros((g, ni, nj, bm, bn), out_dtype)
                out_tiles = out_tiles.at[sg, si, sj].add(masked)
                out_d = out_tiles.transpose(0, 1, 3, 2, 4).reshape(g, mp, np_)
                if emit is None:
                    return out_d
                # Emitted bits ride the same steered scatter as their tiles
                # (dead slots carry zero bits: their accumulator never left 0).
                er, ec = emit
                bits_m = bits_c * live_slot.astype(jnp.int32)[:, None, None]
                bt = jnp.zeros((g, ni, nj, bm // er, bn // ec), jnp.int32)
                bt = bt.at[sg, si, sj].add(bits_m)
                bits = bt.transpose(0, 1, 3, 2, 4).reshape(
                    g, mp // er, np_ // ec)
                return out_d, bits

        if s_cap >= g * ni * nj:
            out = _compact()          # queue provably cannot overflow
        else:
            # Queue-capacity overflow would silently drop live tiles.  The
            # live count is a traced value, so detect at runtime and fall
            # back to the predicated (full-grid) schedule — exact always.
            # Both branches return the same (out[, bits]) pytree.
            if not isinstance(n_live, jax.core.Tracer) \
                    and int(n_live) > s_cap:
                # Concrete dispatch overflowed: count the fallback and
                # attribute it to the spec's autotune key so a persistently
                # overflowing spec can be demoted off the compact schedule
                # (kernels/autotune.py quarantine ladder).
                stats.record("fallback:queue_overflow")
                from . import autotune
                autotune.report_overflow(spec, (m, k, n))
            out = jax.lax.cond(n_live > s_cap, _predicated, _compact)
    else:
        out = _predicated()
    with stats.lifecycle_scope("scatter", spec.schedule):
        if emit is None:
            return out[:, :m, :n]
        er, ec = emit
        out, bits = out
        # Padding tiles are dead (zero accumulators), so the padded bitmap
        # rows/cols are exactly 0 — unpadding to the data's covering grid
        # is exact, matching what a fresh scan of the unpadded output
        # would give.
        return out[:, :m, :n], bits[:, :ceil_to(m, er) // er,
                                    :ceil_to(n, ec) // ec]


# ---------------------------------------------------------------------------
# Bitmap producers (encode/scan) — unchanged contract
# ---------------------------------------------------------------------------

def bitmap_scan(
    x: jnp.ndarray,
    *,
    block: Tuple[int, int] = (DEFAULT_BLOCK[0], DEFAULT_BLOCK[2]),
    kind: str = "act",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas block-any-nonzero bitmap of SIGNED data at granularity
    ``block`` — the encoder for tensors with no ReLU to fuse into (raw
    inputs, incoming gradients).  Pads, launches, unpads.

    Counted under the distinct ``scan_pallas:<kind>`` stats key so the
    audit can tell TPU-native scans from the retained XLA-reference scans
    (``scan:<kind>``); both still count toward the one-computation-per-
    tensor-per-step budget.
    """
    m, n = x.shape
    bm, bn = block
    np_ = ceil_to(n, bn)
    lr = slab_rows(m, bm, np_)
    mp = ceil_to(m, lr)
    stats.record(f"scan_pallas:{kind}")
    with stats.lifecycle_scope("scan", kind):
        x_p = pad_to(x, mp, np_)
        bitmap = bitmap_scan_kernel(x_p, bm=bm, bn=bn, lr=lr, lc=np_,
                                    interpret=_use_interpret(interpret))
        return bitmap[: ceil_to(m, bm) // bm, :]


def relu_encode(
    z: jnp.ndarray,
    *,
    block: Tuple[int, int] = (DEFAULT_BLOCK[0], DEFAULT_BLOCK[2]),
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused relu(z) + block bitmap at granularity ``block``.

    Pads, launches, unpads.  The launch tile is decoupled from the bitmap
    granularity (``shapes.slab_rows``: lane-dense slabs of up to a few
    thousand rows), so fine granularities — down to per-row bitmaps, which
    the conv path needs for im2col-derivable metadata — stay cheap to
    launch.

    This is THE forward-pass bitmap computation: one fused pass per
    activation per step; every downstream mask is derived from its result.
    """
    m, n = z.shape
    bm, bn = block
    np_ = ceil_to(n, bn)
    lr = slab_rows(m, bm, np_)
    mp = ceil_to(m, lr)
    stats.record("encode:act")
    with stats.lifecycle_scope("encode", "act"):
        z_p = pad_to(z, mp, np_)
        y, bitmap = relu_encode_kernel(z_p, bm=bm, bn=bn, lr=lr, lc=np_,
                                       interpret=_use_interpret(interpret))
        return y[:m, :n], bitmap[: ceil_to(m, bm) // bm, :]


# ---------------------------------------------------------------------------
# The paper's composite ops, spec-driven
# ---------------------------------------------------------------------------

def relu_bwd_masked(
    dy: jnp.ndarray,          # (M, K) δ_post — gradient arriving from layer above
    w_t: jnp.ndarray,         # (K, N) Wᵀ of the producer layer
    relu_mask: jnp.ndarray,   # (M, N) {0,1} σ'(z) captured in the forward pass
    *,
    spec: Optional[GemmSpec] = None,
    use_input_sparsity: bool = True,
    use_output_sparsity: bool = True,
) -> jnp.ndarray:
    """δ_pre = (δ_post @ Wᵀ) ⊙ σ'(z) with block skipping — the paper's core op.

    OUTPUT sparsity: tiles where σ'(z) is all-zero are never computed.
    INPUT sparsity: K-tiles of δ_post that are all-zero are skipped.
    Partially-live tiles are computed densely then Hadamard-masked — exact
    (the σ′ multiply rides the kernel's fused epilogue).  ``spec`` carries
    tile shape / schedule / queue builder; its epilogue field is forced to
    ``sigma_prime`` since this op IS the fused-epilogue GEMM.
    """
    spec = GemmSpec() if spec is None else spec
    spec = spec.with_(epilogue="sigma_prime", groups=1)
    bm, bk, bn = spec.block
    mask32 = relu_mask.astype(jnp.float32)
    out_mask = block_bitmap(mask32, bm, bn) if use_output_sparsity else None
    a_mask = block_bitmap(dy.astype(jnp.float32), bm, bk) \
        if use_input_sparsity else None
    return sparse_gemm(dy, w_t, GemmMasks(out_mask, a_mask, None), spec,
                       epilogue_mult=mask32)


def weight_grad_masked(
    x_t: jnp.ndarray,        # (N, M) Xᵀ — activations (sparse post-ReLU)
    dy: jnp.ndarray,         # (N, K) δ — gradient (sparse post-ReLU-Hadamard)
    *,
    spec: Optional[GemmSpec] = None,
    use_input_sparsity: bool = True,
) -> jnp.ndarray:
    """dW = Xᵀ @ δ with INPUT sparsity on both operands (the paper's WG stage).

    There is no output sparsity in WG — every weight gradient entry is
    needed — but the contraction (batch·spatial) dimension tiles where
    either operand is all-zero are skipped.
    """
    spec = GemmSpec() if spec is None else spec
    spec = spec.with_(epilogue="none", groups=1)
    bm, bk, bn = spec.block
    a_mask = b_mask = None
    if use_input_sparsity:
        a_mask = block_bitmap(x_t.astype(jnp.float32), bm, bk)
        b_mask = block_bitmap(dy.astype(jnp.float32), bk, bn)
    return sparse_gemm(x_t, dy, GemmMasks(None, a_mask, b_mask), spec)
