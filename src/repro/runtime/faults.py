"""Seeded, deterministic fault injection — and the chaos matrix that proves
each fault class is DETECTED (the intended guard fires), ATTRIBUTED (the
right ``guard:*`` / decision-log entry names it) and SURVIVED (training or
the artifact stays within tolerance of the un-faulted run).

Faults are addressed by SITE.  Sites are instrumented as hooks at the layer
that owns them — the kernels/core/checkpoint layers never import this
module; this module installs into their ``set_*_hook`` slots:

  train:params          NaN/Inf planted in the params pytree before one
                        step (corrupt activations → non-finite loss; heals
                        in one step: the optimizer's master weights
                        regenerate the params after the skipped update)
  train:opt_state       NaN/Inf planted in optimizer state (PERSISTENT
                        corruption: every later step is non-finite until a
                        rollback restores an intact checkpoint)
  gemm:spec             compact-queue capacity shrunk at dispatch
                        (``max_active_blocks``) — forces queue overflow
  gemm:emit_bits        bit flipped in an emitted dy bitmap
  registry:register     grad-bitmap registrations dropped (the hand-off
                        fault: emitted bitmaps never reach consumers)
  collective:allreduce  one shard's live-block contribution zeroed inside
                        the bitmap-compressed gradient all-reduce (the
                        transport-corruption class; the dense paths sit
                        outside the tamper point)
  checkpoint:post_leaves / checkpoint:pre_commit
                        the checkpoint writer crashes at that protocol
                        point (``InjectedCrash``)

``python -m repro.runtime.faults --matrix`` runs the whole catalogue (the
CI ``chaos`` job adds ``--fail-on-undetected`` and archives the CSV).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Callable, Dict, List, Optional

from repro.kernels import autotune, stats

# site → the fault kinds that make sense there (validated at arm time).
SITES: Dict[str, tuple] = {
    "train:params": ("nan", "inf"),
    "train:opt_state": ("nan", "inf"),
    "gemm:spec": ("queue_overflow",),
    "gemm:emit_bits": ("bitmap_flip",),
    "registry:register": ("registry_drop",),
    "collective:allreduce": ("drop_contrib",),
    "checkpoint:post_leaves": ("crash",),
    "checkpoint:pre_commit": ("crash",),
}


class InjectedCrash(RuntimeError):
    """Raised by an armed ``crash`` fault at its checkpoint protocol point —
    stands in for the writer process dying there."""


@dataclasses.dataclass
class Fault:
    """One armed fault.  ``step`` gates the stepped sites (``train:*``) to
    a single training step; ``seed`` makes the corrupted element/bit/
    capacity deterministic.  ``fired`` counts injections."""
    site: str
    kind: str
    step: Optional[int] = None
    seed: int = 0
    fired: int = 0


_ARMED: Dict[str, Fault] = {}
_PREV_HOOKS: Optional[tuple] = None


def arm(fault: Fault) -> Fault:
    """Arm ``fault`` at its site (replacing any fault already there) and
    install the layer hooks on first use."""
    if fault.site not in SITES:
        raise ValueError(f"unknown fault site {fault.site!r}; "
                         f"one of {sorted(SITES)}")
    if fault.kind not in SITES[fault.site]:
        raise ValueError(f"fault kind {fault.kind!r} not valid at "
                         f"{fault.site!r} (allowed: {SITES[fault.site]})")
    _ARMED[fault.site] = fault
    _install_hooks()
    return fault


def disarm(site: Optional[str] = None) -> None:
    """Disarm one site (or, with None, everything) and restore the layers'
    previous hooks once nothing is armed."""
    if site is None:
        _ARMED.clear()
    else:
        _ARMED.pop(site, None)
    if not _ARMED:
        _uninstall_hooks()


def active() -> Dict[str, Fault]:
    return dict(_ARMED)


def _install_hooks() -> None:
    global _PREV_HOOKS
    if _PREV_HOOKS is not None:
        return
    from repro import checkpoint as ckpt
    from repro.core import sparse_tensor
    from repro.kernels import ops
    from repro.sharding import collectives
    _PREV_HOOKS = (
        ops.set_tamper_hook(_tamper_hook),
        sparse_tensor.set_register_hook(_register_hook),
        ckpt.set_crash_hook(_crash_hook),
        collectives.set_collective_hook(_collective_hook),
    )


def _uninstall_hooks() -> None:
    global _PREV_HOOKS
    if _PREV_HOOKS is None:
        return
    from repro import checkpoint as ckpt
    from repro.core import sparse_tensor
    from repro.kernels import ops
    from repro.sharding import collectives
    tamper, register, crash, collective = _PREV_HOOKS
    ops.set_tamper_hook(tamper)
    sparse_tensor.set_register_hook(register)
    ckpt.set_crash_hook(crash)
    collectives.set_collective_hook(collective)
    _PREV_HOOKS = None


# ---------------------------------------------------------------------------
# The injections
# ---------------------------------------------------------------------------

def _tamper_hook(site: str, value):
    f = _ARMED.get(site)
    if f is None:
        return value
    if site == "gemm:spec" and f.kind == "queue_overflow":
        if value.schedule != "compact":
            return value          # nothing to overflow on other schedules
        f.fired += 1
        return value.with_(max_active_blocks=1 + f.seed % 2)
    if site == "gemm:emit_bits" and f.kind == "bitmap_flip":
        f.fired += 1
        return _flip_bit(value, f.seed)
    return value


def _flip_bit(bits, seed: int):
    import jax.numpy as jnp
    flat = jnp.reshape(bits, (-1,))
    idx = seed % flat.shape[0]
    flat = flat.at[idx].set(1 - flat[idx])
    return jnp.reshape(flat, bits.shape)


def _register_hook(obj, bitmap, gran):
    f = _ARMED.get("registry:register")
    if f is not None and f.kind == "registry_drop":
        f.fired += 1
        return False              # veto: the hand-off never happens
    return True


def _collective_hook(site: str, contrib, axis_name):
    """Zero the compact-buffer contribution of ONE shard (``seed`` picks
    which, mod the axis size) inside the compressed all-reduce — the
    collective analogue of a torn write: blocks only that shard owned
    arrive as zeros while the psum'd union bitmap still marks them live.
    ``fired`` counts traces, not executions (jit caches the traced
    tamper)."""
    f = _ARMED.get(site)
    if f is None or f.kind != "drop_contrib":
        return contrib
    import jax.numpy as jnp
    from jax import lax
    f.fired += 1
    name = axis_name if isinstance(axis_name, str) else axis_name[0]
    idx = lax.axis_index(name)
    n = lax.axis_size(name)
    keep = (idx != jnp.mod(f.seed, n)).astype(contrib.dtype)
    return contrib * keep


def _crash_hook(name: str) -> None:
    f = _ARMED.get(name)
    if f is not None and f.kind == "crash":
        f.fired += 1
        raise InjectedCrash(name)


def tap(site: str, value, *, step: Optional[int] = None):
    """Train-loop-side injection point (``launch.train.train_loop`` offers
    its params/opt-state pytrees here each step).  Zero-cost passthrough
    when the site is unarmed or gated to a different step."""
    f = _ARMED.get(site)
    if f is None or f.kind not in ("nan", "inf"):
        return value
    if f.step is not None and step != f.step:
        return value
    f.fired += 1
    return _plant_nonfinite(value, f.kind, f.seed)


def _plant_nonfinite(tree, kind: str, seed: int):
    """Deterministically overwrite one element of one float leaf with
    NaN/Inf (seed picks leaf and element)."""
    import jax
    import jax.numpy as jnp
    leaves, tdef = jax.tree_util.tree_flatten(tree)
    float_idx = [i for i, l in enumerate(leaves)
                 if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)
                 and jnp.asarray(l).size > 0]
    if not float_idx:
        return tree
    i = float_idx[seed % len(float_idx)]
    leaf = jnp.asarray(leaves[i])
    bad = jnp.asarray(float("nan") if kind == "nan" else float("inf"),
                      dtype=leaf.dtype)
    flat = jnp.reshape(leaf, (-1,))
    flat = flat.at[seed % flat.shape[0]].set(bad)
    leaves[i] = jnp.reshape(flat, leaf.shape)
    return jax.tree_util.tree_unflatten(tdef, leaves)


# ---------------------------------------------------------------------------
# The chaos matrix — every fault class: inject, detect, attribute, survive
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MatrixRow:
    fault: str
    site: str
    kind: str
    detected: bool
    guard_key: str       # the guard:* / decision-log entry that named it
    survived: bool
    detail: str

    @property
    def ok(self) -> bool:
        return self.detected and self.survived


def _fresh(**autotune_kwargs) -> None:
    stats.reset()
    autotune.reset(**autotune_kwargs)
    disarm()


def _train(*, guard=None, ckpt_dir=None, steps=6, ckpt_every=0, seed=3):
    from repro.configs import SMOKE_ARCHS
    from repro.configs.base import TrainConfig
    from repro.launch.train import train_loop
    cfg = SMOKE_ARCHS["smollm-360m"]
    tcfg = TrainConfig(total_steps=steps, checkpoint_every=ckpt_every,
                       learning_rate=1e-3, seed=seed)
    return train_loop(cfg, tcfg, batch_size=4, seq_len=16, steps=steps,
                      ckpt_dir=ckpt_dir, log_every=0, guard=guard)


def _case_params_nonfinite() -> MatrixRow:
    """NaN planted in params for ONE step → non-finite loss/grads; the
    optimizer skips the update and the master weights regenerate clean
    params — one ``skip`` verdict, no lasting damage."""
    import jax
    from .guards import StepGuard
    _fresh()
    base = _train()["losses"][-1]
    guard = StepGuard()
    arm(Fault("train:params", "nan", step=2, seed=7))
    try:
        out = _train(guard=guard)
    finally:
        disarm()
    jax.effects_barrier()
    g = stats.guard_counts()
    verdicts = [v for _, v in guard.verdicts]
    detected = g.get("guard:nonfinite_skip", 0) >= 1 and "skip" in verdicts
    survived = abs(out["losses"][-1] - base) < 0.5
    return MatrixRow(
        "params-nan-one-step", "train:params", "nan", detected,
        "guard:nonfinite_skip", survived,
        f"verdicts={verdicts} final={out['losses'][-1]:.4f} base={base:.4f}")


def _case_optstate_rollback() -> MatrixRow:
    """NaN planted in optimizer state → PERSISTENT non-finite steps (the
    corruption lives in the master weights, skipping can't heal it); the
    guard escalates past the skip budget to a rollback, restoring the
    newest intact checkpoint, and training converges again."""
    import math
    import tempfile

    import jax
    from .guards import GuardConfig, StepGuard
    _fresh()
    base = _train(steps=10)["losses"][-1]
    guard = StepGuard(GuardConfig(max_consecutive_skips=2))
    arm(Fault("train:opt_state", "nan", step=4, seed=11))
    try:
        with tempfile.TemporaryDirectory() as d:
            out = _train(guard=guard, ckpt_dir=d, steps=10, ckpt_every=2)
    finally:
        disarm()
    jax.effects_barrier()
    g = stats.guard_counts()
    verdicts = [v for _, v in guard.verdicts]
    final = out["losses"][-1]
    detected = g.get("guard:verdict:rollback", 0) >= 1 \
        and g.get("guard:nonfinite_skip", 0) >= 1
    survived = math.isfinite(final) and abs(final - base) < 1.0 \
        and verdicts[-1] == "ok"
    return MatrixRow(
        "optstate-nan-persistent", "train:opt_state", "nan", detected,
        "guard:verdict:rollback", survived,
        f"verdicts={verdicts} final={final:.4f} base={base:.4f}")


def _case_bitmap_flip() -> MatrixRow:
    """Bit flipped in an emitted bitmap → the guard's consistency probe
    catches it, hands back the rescanned (trusted) bitmap, and the degrade
    path books the producing spec as a suspect."""
    import numpy as np

    from repro.core import policy as pol
    from repro.kernels.ops import sparse_gemm
    from .guards import StepGuard, reference_bitmap
    _fresh()
    rng = np.random.default_rng(0)
    a = (rng.standard_normal((16, 12)) * (rng.random((16, 12)) > 0.6)
         ).astype(np.float32)
    b = rng.standard_normal((12, 16)).astype(np.float32)
    P = pol.IN_OUT.with_(kernel_impl="pallas", block=(8, 8, 8))
    dims = (16, 12, 16)
    spec = P.gemm_spec(dims=dims).with_(
        epilogue=("bitmap_emit",), emit_gran=(4, 4))
    guard = StepGuard()
    arm(Fault("gemm:emit_bits", "bitmap_flip", seed=5))
    try:
        out, bits = sparse_gemm(a, b, None, spec=spec)
    finally:
        disarm()
    ok, corrected = guard.probe_emit(out, bits, (4, 4), spec=spec, dims=dims)
    demoted = guard.degrade()
    g = stats.guard_counts()
    ref = reference_bitmap(np.asarray(out), (4, 4))
    detected = (not ok) and g.get("guard:bitmap_mismatch", 0) >= 1 \
        and len(demoted) >= 1
    survived = bool(np.array_equal(np.asarray(corrected), ref)) \
        and np.allclose(np.asarray(out), a @ b, atol=1e-4)
    return MatrixRow(
        "emitted-bitmap-bit-flip", "gemm:emit_bits", "bitmap_flip", detected,
        "guard:bitmap_mismatch", survived,
        f"probe_ok={ok} demoted={[k.stats_key for k in demoted]}")


def _case_queue_overflow_demote() -> MatrixRow:
    """Compact-queue capacity shrunk at dispatch → every dispatch
    overflows (counted, exact fallback); past the threshold the autotuner
    demotes the key off the compact schedule, with a ``demote:overflow``
    decision-log event — the persistently-overflowing spec stops paying
    for queue construction."""
    import numpy as np

    from repro.core import policy as pol
    from repro.kernels.ops import GemmMasks, sparse_gemm
    _fresh(overflow_demote_after=4)
    rng = np.random.default_rng(1)
    a = rng.standard_normal((16, 16)).astype(np.float32)
    b = rng.standard_normal((16, 16)).astype(np.float32)
    mask = np.array([[1, 1], [1, 0]], dtype=np.int32)   # 3 of 4 tiles live
    ref = a @ b
    for i in range(2):
        for j in range(2):
            if not mask[i, j]:
                ref[i * 8:(i + 1) * 8, j * 8:(j + 1) * 8] = 0.0
    P = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))
    dims = (16, 16, 16)
    exact = True
    arm(Fault("gemm:spec", "queue_overflow", seed=0))
    try:
        for _ in range(6):
            spec = P.gemm_spec(dims=dims)
            out = sparse_gemm(a, b, GemmMasks(out=mask), spec=spec)
            exact = exact and np.allclose(np.asarray(out), ref, atol=1e-4)
        after = P.gemm_spec(dims=dims)
    finally:
        disarm()
    c = stats.counts()
    demote_rows = [r for r in autotune.log_rows()
                   if r["event"] == "demote:overflow"]
    detected = c.get("fallback:queue_overflow", 0) >= 4 \
        and len(demote_rows) >= 1
    survived = exact and after.schedule == "predicated" \
        and c.get("guard:quarantine_clamp", 0) >= 1
    return MatrixRow(
        "compact-queue-overflow", "gemm:spec", "queue_overflow", detected,
        "autotune-log:demote:overflow", survived,
        f"overflows={c.get('fallback:queue_overflow', 0)} "
        f"after_schedule={after.schedule} "
        f"demoted_key={demote_rows[0]['key'] if demote_rows else None}")


def _case_registry_drop() -> MatrixRow:
    """Grad-bitmap registrations dropped → consumers miss their dy masks.
    The miss-counter delta (above the structural baseline — the loss
    cotangent never has a producer) is the detection; numerics must be
    unchanged (a lost mask degrades to lost skipping, never wrong math)."""
    import jax
    import numpy as np

    from repro.core import policy as pol
    from repro.core.sparse_linear import relu_matmul
    from .guards import StepGuard
    _fresh()
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((33, 31)) * (rng.random((33, 31)) > 0.5)
         ).astype(np.float32)
    w1 = rng.standard_normal((31, 24)).astype(np.float32)
    w2 = rng.standard_normal((24, 18)).astype(np.float32)
    P = pol.IN_OUT.with_(kernel_impl="pallas", block=(16, 16, 16))

    def loss(x, w1, w2):
        return (relu_matmul(relu_matmul(x, w1, P), w2, P) ** 2).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    base_grads = grad(x, w1, w2)
    baseline_misses = stats.counts().get("registry:miss", 0)
    guard = StepGuard()
    guard.scan_counters()                      # set the delta baseline
    arm(Fault("registry:register", "registry_drop"))
    try:
        faulted_grads = grad(x, w1, w2)
    finally:
        disarm()
    deltas = guard.scan_counters(
        expected_registry_misses=baseline_misses)
    g = stats.guard_counts()
    detected = deltas["registry:miss"] > baseline_misses \
        and g.get("guard:registry_miss", 0) >= 1
    survived = all(
        np.allclose(np.asarray(gb), np.asarray(gf), atol=1e-5)
        for gb, gf in zip(base_grads, faulted_grads))
    return MatrixRow(
        "grad-bitmap-registry-drop", "registry:register", "registry_drop",
        detected, "guard:registry_miss", survived,
        f"misses: baseline={baseline_misses} faulted={deltas['registry:miss']}")


def _case_collective_drop() -> MatrixRow:
    """One shard's live-block contribution zeroed inside the compressed
    gradient all-reduce → blocks only that shard owned arrive all-zero
    while the psum'd union bitmap still marks them live; the guard's
    consistency probe (``probe_emit`` on the summed gradient against the
    union bits) flags the disagreement, and the summed grad-norm drops
    below the clean reduce's.  Survival: the dense path sits OUTSIDE the
    tamper point — the same reduce with ``cutoff=1.0`` (capacity ≥ every
    block ⇒ dense psum) is exact under the still-armed fault, which is
    precisely the degradation ladder's fallback story."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.sharding import collectives
    from .guards import StepGuard
    _fresh()
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("data",))
    gran, m, n = (4, 4), 16, 16                # 4×4 grid = 16 blocks
    rng = np.random.default_rng(3)
    # Shard 0 (the one the seed drops) exclusively owns block (0, 0);
    # blocks (1, 1) and (2, 2) are live on every shard; the rest is dead.
    shards = np.zeros((n_dev, m, n), np.float32)
    shards[0, 0:4, 0:4] = rng.standard_normal((4, 4))
    for s in range(n_dev):
        shards[s, 4:8, 4:8] = rng.standard_normal((4, 4))
        shards[s, 8:12, 8:12] = rng.standard_normal((4, 4))
    bms = (np.abs(shards).reshape(n_dev, 4, 4, 4, 4).sum(axis=(2, 4)) > 0
           ).astype(np.int32)

    def _reduce(cutoff):
        def body(xs, bs):
            return collectives.sparse_psum(
                xs[0], bs[0], gran, axis_name="data", cutoff=cutoff,
                return_bits=True)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False))

    guard = StepGuard()
    fault = arm(Fault("collective:allreduce", "drop_contrib", seed=0))
    try:
        out, union = _reduce(0.5)(jnp.asarray(shards), jnp.asarray(bms))
        out_dense, _ = _reduce(1.0)(jnp.asarray(shards), jnp.asarray(bms))
    finally:
        disarm()
    ref = shards.sum(0)
    ok_probe, _ = guard.probe_emit(np.asarray(out), np.asarray(union), gran)
    g = stats.guard_counts()
    c = stats.counts()
    norm_drop = 1.0 - float(np.linalg.norm(np.asarray(out))
                            / np.linalg.norm(ref))
    detected = (not ok_probe) and g.get("guard:bitmap_mismatch", 0) >= 1 \
        and fault.fired >= 1 and norm_drop > 0.0
    survived = bool(np.allclose(np.asarray(out_dense), ref, atol=1e-5)) \
        and c.get("collective:dense", 0) >= 1
    return MatrixRow(
        "collective-drop-contrib", "collective:allreduce", "drop_contrib",
        detected, "guard:bitmap_mismatch", survived,
        f"probe_ok={ok_probe} norm_drop={norm_drop:.3f} "
        f"dense_exact={survived} devices={n_dev}")


def _case_ckpt_crash_mid_save() -> MatrixRow:
    """Checkpoint writer dies between the payload write and the commit
    rename → the partial ``.tmp`` dir is never visible as a checkpoint,
    restore lands on the previous intact step, and the next save clears
    the wreckage."""
    import os
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from repro import checkpoint as ckpt
    _fresh()
    tree2 = {"w": jnp.arange(6, dtype=jnp.float32)}
    tree4 = {"w": jnp.arange(6, dtype=jnp.float32) * 2}
    crashed = wreckage = False
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 2, tree2)
        arm(Fault("checkpoint:pre_commit", "crash"))
        try:
            ckpt.save(d, 4, tree4)
        except InjectedCrash:
            crashed = True
        finally:
            disarm()
        wreckage = any(n.endswith(".tmp") for n in os.listdir(d))
        visible = ckpt.latest_step(d)
        step, back = ckpt.restore(d, tree2)
        restored_prev = step == 2 and np.array_equal(
            np.asarray(back["w"]), np.asarray(tree2["w"]))
        ckpt.save(d, 4, tree4)                 # healthy retry
        cleaned = not any(n.endswith(".tmp") for n in os.listdir(d))
    detected = crashed and wreckage and visible == 2
    survived = restored_prev and cleaned
    return MatrixRow(
        "ckpt-crash-pre-commit", "checkpoint:pre_commit", "crash", detected,
        "commit-protocol", survived,
        f"crashed={crashed} visible={visible} cleaned={cleaned}")


def _case_ckpt_corrupt_newest() -> MatrixRow:
    """Newest COMMITTED checkpoint corrupted on disk (truncated payload) →
    auto-resume detects the typed corruption, counts the fallback, lands
    on the previous intact step and quarantines the wreck."""
    import os
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from repro import checkpoint as ckpt
    _fresh()
    tree2 = {"w": jnp.arange(6, dtype=jnp.float32)}
    tree4 = {"w": jnp.arange(6, dtype=jnp.float32) * 2}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 2, tree2)
        ckpt.save(d, 4, tree4)
        npz = os.path.join(d, "step_00000004", "leaves.npz")
        with open(npz, "r+b") as f:
            f.truncate(16)                     # torn write
        step, back = ckpt.restore(d, tree2)
        quarantined = any(n.endswith(".corrupt") for n in os.listdir(d))
        g = stats.guard_counts()
        restored_prev = step == 2 and np.array_equal(
            np.asarray(back["w"]), np.asarray(tree2["w"]))
    detected = g.get("guard:ckpt_fallback", 0) >= 1 and quarantined
    survived = restored_prev
    return MatrixRow(
        "ckpt-corrupt-newest", "checkpoint:pre_commit", "crash", detected,
        "guard:ckpt_fallback", survived,
        f"fallbacks={g.get('guard:ckpt_fallback', 0)} "
        f"quarantined={quarantined}")


CASES: List[Callable[[], MatrixRow]] = [
    _case_params_nonfinite,
    _case_optstate_rollback,
    _case_bitmap_flip,
    _case_queue_overflow_demote,
    _case_registry_drop,
    _case_collective_drop,
    _case_ckpt_crash_mid_save,
    _case_ckpt_corrupt_newest,
]


def run_matrix(names: Optional[List[str]] = None) -> List[MatrixRow]:
    """Run the fault catalogue (optionally filtered by substring) and
    return one row per case.  Each case isolates its own stats/autotune
    state and disarms its faults on the way out."""
    rows = []
    for case in CASES:
        label = case.__name__.replace("_case_", "")
        if names and not any(n in label for n in names):
            continue
        try:
            rows.append(case())
        except Exception as e:                     # noqa: BLE001
            rows.append(MatrixRow(label, "?", "?", False, "", False,
                                  f"case crashed: {e!r}"))
        finally:
            disarm()
    _fresh()
    return rows


def write_csv(rows: List[MatrixRow], path: str) -> None:
    import csv
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["fault", "site", "kind", "detected", "guard_key",
                    "survived", "ok", "detail"])
        for r in rows:
            w.writerow([r.fault, r.site, r.kind, r.detected, r.guard_key,
                        r.survived, r.ok, r.detail])


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Chaos matrix: inject every fault class, assert each "
                    "is detected, attributed and survived.")
    ap.add_argument("--matrix", action="store_true",
                    help="run the full fault catalogue")
    ap.add_argument("--only", nargs="*", default=None,
                    help="substring filter on case names")
    ap.add_argument("--csv", default=None, help="write results as CSV")
    ap.add_argument("--fail-on-undetected", action="store_true",
                    help="exit 1 if any fault goes undetected or unsurvived")
    args = ap.parse_args(argv)
    if not args.matrix:
        ap.print_help()
        return 0
    rows = run_matrix(args.only)
    width = max(len(r.fault) for r in rows) + 2
    for r in rows:
        mark = "PASS" if r.ok else "FAIL"
        print(f"{mark}  {r.fault:<{width}} detected={str(r.detected):<5} "
              f"survived={str(r.survived):<5} via {r.guard_key}")
        print(f"      {r.detail}")
    if args.csv:
        write_csv(rows, args.csv)
        print(f"wrote {args.csv}")
    bad = [r for r in rows if not r.ok]
    print(f"{len(rows) - len(bad)}/{len(rows)} fault classes detected "
          f"and survived")
    if bad and args.fail_on_undetected:
        return 1
    return 0


if __name__ == "__main__":
    # ``python -m repro.runtime.faults`` executes this file as __main__,
    # while the train loop imports ``repro.runtime.faults`` — two module
    # instances, two _ARMED dicts.  Delegate to the canonical instance so
    # armed faults are the ones the instrumented layers consult.
    from repro.runtime import faults as _canonical
    sys.exit(_canonical.main())
