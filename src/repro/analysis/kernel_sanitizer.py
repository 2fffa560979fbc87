"""Interpret-mode shadow execution of the Pallas kernels, with tripwires.

``pl.pallas_call(..., interpret=True)`` checks VALUES; it cannot see a
write that lands twice, a store that strays outside the padded grid, or a
stale accumulator read — those all still produce *some* value.  This
module re-executes the kernel *functions* (the plain Python bodies in
``kernels/masked_matmul.py``) over numpy shadow memory with every ref
access instrumented:

  ACC_READ_BEFORE_WRITE  a VMEM accumulator is read (``+=`` reads!) in an
                         output tile's K-chain before that chain zeroed it
                         — silent reuse of the previous tile's partial sums.
  DOUBLE_WRITE           an output tile is written more than once (or never)
                         across the grid; the contract is exactly one
                         writeback per tile, at the last K step.
  STORE_OOB              a store outside the ref's padded block window
                         (numpy would silently wrap negative indices; the
                         shadow ref bounds-checks *before* storing).

The kernel bodies only reach ``pl`` / ``jnp`` / ``jax`` through module
globals, so a shadow run swaps those globals for shims for the duration of
the call — no kernel code changes, and the *same* function objects that the
real ``pallas_call`` launches are the ones audited.  Each driver hard-codes
its kernel family's grid / BlockSpec geometry: that geometry IS part of the
static contract being checked, not an input.  ``kernel_fn=`` overrides let
the self-tests plant mutant kernels and prove every tripwire fires
(tests/test_analysis.py).
"""
from __future__ import annotations

import importlib
import sys
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

from .report import Violation


# ---------------------------------------------------------------------------
# Shadow memory
# ---------------------------------------------------------------------------

class ShadowRef:
    """Backing store for one ref: data + per-element write counts.

    ``epochal`` refs model the VMEM accumulator: the driver bumps ``epoch``
    when a new output tile's K-chain begins, and a read while
    ``last_write_epoch < epoch`` is a stale read.
    """

    def __init__(self, shape, dtype, name: str, *, epochal: bool = False):
        self.data = np.zeros(shape, dtype)
        self.writes = np.zeros(shape, np.int64)
        self.name = name
        self.epochal = epochal
        self.epoch = 0
        self.last_write_epoch = -1


def input_ref(arr: np.ndarray, name: str) -> ShadowRef:
    """An input operand wrapped as shadow memory (kernels must not write
    inputs; if one did, its write counts would expose it)."""
    s = ShadowRef(arr.shape, arr.dtype, name)
    s.data = np.asarray(arr)
    return s


class RefView:
    """One grid step's window onto a ShadowRef (emulates the BlockSpec)."""

    def __init__(self, shadow: ShadowRef, window, san: "_Sanitizer"):
        self.shadow = shadow
        self.window = window          # tuple of slices into shadow.data
        self.san = san

    @property
    def dtype(self):
        return self.shadow.data.dtype

    @property
    def shape(self):
        return self.shadow.data[self.window].shape

    def _sel(self, idx):
        """Index normalized against the window; None if out of bounds."""
        view_shape = self.shape
        if idx is Ellipsis:
            idx = (slice(None),) * len(view_shape)
        if not isinstance(idx, tuple):
            idx = (idx,)
        idx = tuple(int(x) if isinstance(x, (np.integer, np.ndarray))
                    and np.ndim(x) == 0 else x for x in idx)
        for d, x in enumerate(idx):
            if isinstance(x, int):
                if not (0 <= x < view_shape[d]):
                    return None
            elif isinstance(x, slice):
                lo = 0 if x.start is None else int(x.start)
                hi = view_shape[d] if x.stop is None else int(x.stop)
                if lo < 0 or hi > view_shape[d]:
                    return None
        return idx

    def __getitem__(self, idx):
        if self.shadow.epochal \
                and self.shadow.last_write_epoch < self.shadow.epoch:
            self.san.report(
                "ACC_READ_BEFORE_WRITE",
                f"{self.shadow.name} read at {self.san.step_label()} before "
                f"this tile's K-chain initialized it")
        sel = self._sel(idx)
        if sel is None:
            return np.zeros((1,), self.dtype)  # OOB read: inert
        return np.array(self.shadow.data[self.window][sel])

    def __setitem__(self, idx, val):
        sel = self._sel(idx)
        if sel is None:
            self.san.report(
                "STORE_OOB",
                f"store to {self.shadow.name}{idx!r} outside its "
                f"{self.shape} block window at {self.san.step_label()}")
            return
        target = self.shadow.data[self.window]
        counts = self.shadow.writes[self.window]
        counts[sel] += 1
        self.shadow.writes[self.window] = counts
        target[sel] = val
        self.shadow.data[self.window] = target
        self.shadow.last_write_epoch = self.shadow.epoch


# ---------------------------------------------------------------------------
# Module-global shims: pl / jnp / jax as seen from inside a kernel body
# ---------------------------------------------------------------------------

class _PlShim:
    def __init__(self, san: "_Sanitizer"):
        self._san = san

    def program_id(self, d):
        return self._san.grid_point[d]

    def num_programs(self, d):
        return self._san.grid_shape[d]

    @staticmethod
    def when(cond):
        def deco(fn):
            if bool(cond):
                fn()
            return fn
        return deco


class _JnpShim:
    """numpy plus the handful of jnp-isms the kernels use on refs."""

    int32 = np.int32
    float32 = np.float32
    bool_ = np.bool_

    @staticmethod
    def zeros_like(x):
        if isinstance(x, RefView):
            return np.zeros(x.shape, x.dtype)
        return np.zeros_like(x)

    @staticmethod
    def dot(a, b, preferred_element_type=np.float32, precision=None):
        return np.dot(np.asarray(a, np.float32), np.asarray(b, np.float32)) \
            .astype(preferred_element_type)

    def __getattr__(self, name):
        return getattr(np, name)


class _LaxShim:
    Precision = jax.lax.Precision

    @staticmethod
    def broadcasted_iota(dtype, shape, dimension):
        return np.indices(shape, dtype)[dimension]

    @staticmethod
    def dot_general(a, b, dimension_numbers, preferred_element_type=None):
        (ca, cb), batch = dimension_numbers
        assert batch == ((), ()), batch     # the kernels use no batch dims
        return np.tensordot(np.asarray(a, np.float32),
                            np.asarray(b, np.float32), (ca, cb))


class _JaxShim:
    config = jax.config

    def __init__(self):
        self.lax = _LaxShim()


class _Sanitizer:
    """Per-run state: grid position, violation log, and the global swap."""

    def __init__(self, kernel_fn: Callable, workload: str):
        self.kernel_fn = kernel_fn
        self.workload = workload
        self.grid_point: Tuple[int, ...] = ()
        self.grid_shape: Tuple[int, ...] = ()
        self.violations: List[Violation] = []
        self._seen = set()

    def step_label(self) -> str:
        return f"grid{tuple(self.grid_point)}"

    def report(self, code: str, message: str):
        key = (code, message)
        if key in self._seen:
            return
        self._seen.add(key)
        self.violations.append(Violation(
            "kernel", code, f"{self.kernel_fn.__name__}@{self.step_label()}",
            message, self.workload))

    def run(self, grid, step):
        """Iterate the grid in C order (K innermost, matching the TPU's
        sequential grid) calling ``step(point)`` with the kernel's module
        globals shimmed."""
        mod = sys.modules[self.kernel_fn.__module__]
        saved = {n: getattr(mod, n, None) for n in ("pl", "jnp", "jax")}
        mod.pl = _PlShim(self)
        mod.jnp = _JnpShim()
        mod.jax = _JaxShim()
        try:
            self.grid_shape = tuple(grid)
            for point in np.ndindex(*grid):
                self.grid_point = point
                step(point)
        finally:
            for n, v in saved.items():
                setattr(mod, n, v)


def _check_single_writeback(san: _Sanitizer, o: ShadowRef, tiles):
    """Every listed output tile window written exactly once, elementwise."""
    for label, window in tiles:
        w = o.writes[window]
        if (w > 1).any():
            san.report("DOUBLE_WRITE",
                       f"output tile {label} written {int(w.max())} times "
                       f"(contract: once, at the last K step)")
        elif (w == 0).any():
            san.report("DOUBLE_WRITE",
                       f"output tile {label} never written "
                       f"(contract: every tile written once)")


def _tile3(gi, i, j, bm, bn):
    return (slice(gi, gi + 1), slice(i * bm, (i + 1) * bm),
            slice(j * bn, (j + 1) * bn))


def _bits_tile(gi, i, j):
    """Window of tile (gi, i, j) in the (G, Mb, Nb, cp, rp) bits layout."""
    return (slice(gi, gi + 1), slice(i, i + 1), slice(j, j + 1),
            slice(None), slice(None))


# ---------------------------------------------------------------------------
# Drivers — one per kernel family; geometry mirrored from the wrappers
# ---------------------------------------------------------------------------

def run_predicated_grouped(
    a: np.ndarray, b: np.ndarray,            # (G, M, K), (G, K, N)
    out_mask: np.ndarray, a_mask: np.ndarray, b_mask: np.ndarray,
    *, bm: int, bk: int, bn: int,
    epilogue_mult: Optional[np.ndarray] = None,   # (G, M, N)
    emit_gran: Optional[Tuple[int, int]] = None,  # bitmap_emit granularity
    kernel_fn: Optional[Callable] = None,
    workload: str = "",
):
    """Shadow-run the grouped predicated kernel over grid (G, Mb, Nb, Kb).

    With ``emit_gran`` the emitted-bitmap output gets its own shadow ref:
    its stores are bounds-checked and held to the same exactly-one-
    writeback-per-tile contract as the data output."""
    mmk = importlib.import_module("repro.kernels.masked_matmul")
    if kernel_fn is None:
        kernel_fn = mmk.gmm_kernel_variant(epilogue_mult is not None,
                                           emit_gran)
    g, m, k = a.shape
    n = b.shape[2]
    ni, nj, nk = m // bm, n // bn, k // bk

    san = _Sanitizer(kernel_fn, workload)
    o = ShadowRef((g, m, n), np.float32, "o_ref")
    acc = ShadowRef((bm, bn), np.float32, "acc_ref", epochal=True)
    a_s = input_ref(a, "a_ref")
    b_s = input_ref(b, "b_ref")
    mult_s = None if epilogue_mult is None \
        else input_ref(np.asarray(epilogue_mult, np.float32), "mult_ref")
    bits = None
    if emit_gran is not None:
        cp, rp = importlib.import_module(
            "repro.kernels.bits").bits_tile_shape(bm, bn, *emit_gran)
        bits = ShadowRef((g, ni, nj, cp, rp), np.int32, "bits_ref")
    om = np.asarray(out_mask, np.int32).reshape(-1)   # flat, as prefetched
    am = np.asarray(a_mask, np.int32).reshape(-1)
    bmsk = np.asarray(b_mask, np.int32).reshape(-1)

    def step(point):
        gi, i, j, kk = point
        if kk == 0:
            acc.epoch += 1      # a new output tile's K-chain begins
        refs = [
            RefView(a_s, (slice(gi, gi + 1), slice(i * bm, (i + 1) * bm),
                          slice(kk * bk, (kk + 1) * bk)), san),
            RefView(b_s, (slice(gi, gi + 1), slice(kk * bk, (kk + 1) * bk),
                          slice(j * bn, (j + 1) * bn)), san),
        ]
        if mult_s is not None:
            refs.append(RefView(mult_s, _tile3(gi, i, j, bm, bn), san))
        refs.append(RefView(o, _tile3(gi, i, j, bm, bn), san))
        if bits is not None:
            refs.append(RefView(bits, _bits_tile(gi, i, j), san))
        refs.append(RefView(acc, (slice(None), slice(None)), san))
        kernel_fn(om, am, bmsk, *refs)

    san.run((g, ni, nj, nk), step)
    tiles = [(f"(g={gi},i={i},j={j})", _tile3(gi, i, j, bm, bn))
             for gi in range(g) for i in range(ni) for j in range(nj)]
    _check_single_writeback(san, o, tiles)
    if bits is not None:
        btiles = [(f"bits(g={gi},i={i},j={j})", _bits_tile(gi, i, j))
                  for gi in range(g) for i in range(ni) for j in range(nj)]
        _check_single_writeback(san, bits, btiles)
    return san.violations, o.data


def run_compact_grouped(
    a: np.ndarray, b: np.ndarray,            # (G, M, K), (G, K, N)
    gg: np.ndarray, ii: np.ndarray, jj: np.ndarray,   # (S,) queue coords
    n_active: np.ndarray,                    # (1,)
    a_mask: np.ndarray, b_mask: np.ndarray,
    *, bm: int, bk: int, bn: int,
    epilogue_mult: Optional[np.ndarray] = None,   # (G, M, N)
    emit_gran: Optional[Tuple[int, int]] = None,  # bitmap_emit granularity
    kernel_fn: Optional[Callable] = None,
    workload: str = "",
):
    """Shadow-run the grouped compacted kernel over grid (S, Kb)."""
    mmk = importlib.import_module("repro.kernels.masked_matmul")
    g, m, k = a.shape
    ni, nj, nk = m // bm, b.shape[2] // bn, k // bk
    if kernel_fn is None:
        kernel_fn = mmk.gmm_compact_kernel_variant(
            epilogue_mult is not None, emit_gran, tiles=(ni, nj))
    gg = np.asarray(gg, np.int32)
    ii = np.asarray(ii, np.int32)
    jj = np.asarray(jj, np.int32)
    (s_cap,) = ii.shape

    san = _Sanitizer(kernel_fn, workload)
    o = ShadowRef((s_cap, bm, bn), np.float32, "o_ref")
    acc = ShadowRef((1, bm, bn), np.float32, "acc_ref", epochal=True)
    a_s = input_ref(a, "a_ref")
    b_s = input_ref(b, "b_ref")
    mult_s = None if epilogue_mult is None \
        else input_ref(np.asarray(epilogue_mult, np.float32), "mult_ref")
    bits = None
    if emit_gran is not None:
        cp, rp = importlib.import_module(
            "repro.kernels.bits").bits_tile_shape(bm, bn, *emit_gran)
        bits = ShadowRef((s_cap, cp, rp), np.int32, "bits_ref")
    na = np.asarray(n_active, np.int32)
    am = np.asarray(a_mask, np.int32).reshape(-1)   # flat, as prefetched
    bmsk = np.asarray(b_mask, np.int32).reshape(-1)

    def step(point):
        s, kk = point
        if kk == 0:
            acc.epoch += 1
        gi, i, j = int(gg[s]), int(ii[s]), int(jj[s])
        refs = [
            RefView(a_s, (slice(gi, gi + 1), slice(i * bm, (i + 1) * bm),
                          slice(kk * bk, (kk + 1) * bk)), san),
            RefView(b_s, (slice(gi, gi + 1), slice(kk * bk, (kk + 1) * bk),
                          slice(j * bn, (j + 1) * bn)), san),
        ]
        if mult_s is not None:
            refs.append(RefView(mult_s, _tile3(gi, i, j, bm, bn), san))
        refs.append(RefView(
            o, (slice(s, s + 1), slice(None), slice(None)), san))
        if bits is not None:
            refs.append(RefView(
                bits, (slice(s, s + 1), slice(None), slice(None)), san))
        refs.append(RefView(acc, (slice(None),) * 3, san))
        kernel_fn(gg, ii, jj, na, am, bmsk, *refs)

    san.run((s_cap, nk), step)
    tiles = [(f"(s={s})", (slice(s, s + 1), slice(None), slice(None)))
             for s in range(s_cap)]
    _check_single_writeback(san, o, tiles)
    if bits is not None:
        btiles = [(f"bits(s={s})", (slice(s, s + 1), slice(None),
                                    slice(None)))
                  for s in range(s_cap)]
        _check_single_writeback(san, bits, btiles)
    return san.violations, o.data


# ---------------------------------------------------------------------------
# Standard sweep — the kernel half of the zero-violation gate
# ---------------------------------------------------------------------------

def sanitize_all() -> List[Violation]:
    """Shadow-run every launched kernel family on representative sparse
    geometries (half-dead masks, every epilogue combination)."""
    from repro.core.workredist import static_queue_order
    out: List[Violation] = []
    r = np.random.RandomState(0)

    g, m, k, n, bsz = 2, 8, 8, 8, 4
    a = r.randn(g, m, k).astype(np.float32)
    b = r.randn(g, k, n).astype(np.float32)
    om = (r.rand(g, m // bsz, n // bsz) > 0.5).astype(np.int32)
    am = (r.rand(g, m // bsz, k // bsz) > 0.3).astype(np.int32)
    bmm = (r.rand(g, k // bsz, n // bsz) > 0.3).astype(np.int32)
    mult = r.rand(g, m, n).astype(np.float32)

    vs, _ = run_predicated_grouped(a, b, om, am, bmm, bm=bsz, bk=bsz, bn=bsz,
                                   workload="predicated:g2")
    out += vs
    vs, _ = run_predicated_grouped(a, b, om, am, bmm, bm=bsz, bk=bsz, bn=bsz,
                                   epilogue_mult=mult,
                                   workload="predicated:epilogue")
    out += vs
    # bitmap_emit writeback stage: alone, and composed with sigma_prime.
    vs, _ = run_predicated_grouped(a, b, om, am, bmm, bm=bsz, bk=bsz, bn=bsz,
                                   emit_gran=(2, 2),
                                   workload="predicated:emit")
    out += vs
    vs, _ = run_predicated_grouped(a, b, om, am, bmm, bm=bsz, bk=bsz, bn=bsz,
                                   epilogue_mult=mult, emit_gran=(2, 2),
                                   workload="predicated:epilogue+emit")
    out += vs

    # Compacted schedule over the real queue of the same out-mask.
    ni = m // bsz
    flat_om = om.reshape(g * ni, n // bsz)
    fii, fjj, n_live = static_queue_order(flat_om, flat_om.size)
    gg = (fii // ni).astype(np.int32)
    ii = (fii % ni).astype(np.int32)
    na = np.array([n_live], np.int32)
    vs, _ = run_compact_grouped(a, b, gg, ii, fjj, na, am, bmm,
                                bm=bsz, bk=bsz, bn=bsz,
                                workload="compact:g2")
    out += vs
    vs, _ = run_compact_grouped(a, b, gg, ii, fjj, na, am, bmm,
                                bm=bsz, bk=bsz, bn=bsz, epilogue_mult=mult,
                                workload="compact:epilogue")
    out += vs
    vs, _ = run_compact_grouped(a, b, gg, ii, fjj, na, am, bmm,
                                bm=bsz, bk=bsz, bn=bsz, emit_gran=(2, 2),
                                workload="compact:emit")
    out += vs
    vs, _ = run_compact_grouped(a, b, gg, ii, fjj, na, am, bmm,
                                bm=bsz, bk=bsz, bn=bsz, epilogue_mult=mult,
                                emit_gran=(2, 2),
                                workload="compact:epilogue+emit")
    out += vs

    return out
