"""Verifier self-tests: zero violations on main, and every planted
mutation caught by the matching checker (the ISSUE's acceptance gate).

The mutant kernels set ``__module__`` to the real kernel module and import
``pl``/``jnp``/``jax`` from it *inside the body*, so the sanitizer's
module-global shim swap governs them exactly as it governs the real
kernels."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_audit, kernel_sanitizer as ks, lint
from repro.analysis.__main__ import main as analysis_main
from repro.core import policy as pol
from repro.kernels import ops

PALLAS_POLICY = pol.IN_OUT_WR.with_(kernel_impl="pallas", block=(8, 8, 8))


def codes(violations):
    return sorted({v.code for v in violations})


# ---------------------------------------------------------------------------
# Zero violations on main — the analyzer's contract for the shipped code
# ---------------------------------------------------------------------------

def test_ffn_relu_workload_clean():
    vs = jaxpr_audit.audit_fn(jaxpr_audit.WORKLOADS["ffn_relu"](),
                              workload="ffn_relu")
    assert vs == []


@pytest.mark.slow
@pytest.mark.parametrize("name", ["vgg16", "mobilenet"])
def test_cnn_workloads_clean(name):
    vs = jaxpr_audit.audit_fn(jaxpr_audit.WORKLOADS[name](), workload=name)
    assert vs == []


def test_kernel_sweep_clean():
    assert ks.sanitize_all() == []


def test_repo_lint_clean():
    assert lint.lint_paths(["src", "benchmarks", "examples"]) == []


def test_cli_kernel_and_lint_pass(tmp_path, capsys):
    out = tmp_path / "v.json"
    rc = analysis_main(["--fail-on-violation", "--skip", "jaxpr",
                        "--json", str(out)])
    assert rc == 0
    assert out.read_text() == "[]"


# ---------------------------------------------------------------------------
# Planted mutation: re-scanned dy bitmap → RESCAN
# ---------------------------------------------------------------------------

def test_mutation_rescanned_dy_bitmap():
    def rescan(dy):
        b1 = ops.bitmap_scan(dy, block=(8, 8), kind="grad")
        b2 = ops.bitmap_scan(dy, block=(8, 8), kind="grad")  # the mutation
        return b1.sum() + b2.sum()

    vs = jaxpr_audit.audit_fn(rescan, jnp.ones((16, 16)), workload="mut")
    assert "RESCAN" in codes(vs)


def test_scan_then_derive_is_not_a_rescan():
    from repro.core.sparse_tensor import coarsen_bitmap

    def ok(dy):
        b = ops.bitmap_scan(dy, block=(8, 8), kind="grad")
        return coarsen_bitmap(b, (1, 1), (2, 2)).sum()

    vs = jaxpr_audit.audit_fn(ok, jnp.ones((16, 16)), workload="ok")
    assert vs == []


# ---------------------------------------------------------------------------
# Planted mutation: dense GEMM on the hot path → DENSE_GEMM
# ---------------------------------------------------------------------------

def test_mutation_dense_fallback_gemm():
    def dense(x, w):
        return (x @ w).sum()   # dot_general outside any dispatch region

    vs = jaxpr_audit.audit_fn(dense, jnp.ones((16, 16)), jnp.ones((16, 16)),
                              workload="mut")
    assert "DENSE_GEMM" in codes(vs)


def test_mutation_adhoc_spec():
    def adhoc(x, w):
        spec = ops.GemmSpec(block=(8, 8, 8), schedule="predicated")
        bm = ops.bitmap_scan(x, block=(8, 8), kind="act")
        return ops.sparse_gemm(x, w, (bm, None), spec).sum()

    vs = jaxpr_audit.audit_fn(adhoc, jnp.ones((16, 16)), jnp.ones((16, 16)),
                              workload="mut")
    assert "SPEC_UNRESOLVED" in codes(vs)


def test_mutation_hand_rolled_mask():
    def underived(x, w):
        bm = (jnp.abs(x[:8, :8]).sum() > 0).astype(jnp.int32) \
            * jnp.ones((2, 2), jnp.int32)
        spec = PALLAS_POLICY.gemm_spec(dims=(16, 16, 16))
        return ops.sparse_gemm(x, w, (bm, None), spec).sum()

    vs = jaxpr_audit.audit_fn(underived, jnp.ones((16, 16)),
                              jnp.ones((16, 16)), workload="mut")
    assert "UNDERIVED_MASK" in codes(vs)


def test_mutation_dense_schedule():
    dense_pol = pol.IN_OUT.with_(kernel_impl="xla")

    def step(x, w):
        bm = ops.bitmap_scan(x, block=(8, 8), kind="act")
        spec = dense_pol.gemm_spec(dims=(16, 16, 16))
        return ops.sparse_gemm(x, w, (bm, None), spec).sum()

    vs = jaxpr_audit.audit_fn(step, jnp.ones((16, 16)), jnp.ones((16, 16)),
                              workload="mut", expect_pallas=True)
    assert "DENSE_SCHEDULE" in codes(vs)


# ---------------------------------------------------------------------------
# Planted mutation: double-written tile → DOUBLE_WRITE (kernel sanitizer)
# ---------------------------------------------------------------------------

def _geometry():
    r = np.random.RandomState(0)
    g, m, k, n, b = 1, 8, 8, 8, 4
    a = r.randn(g, m, k).astype(np.float32)
    bb = r.randn(g, k, n).astype(np.float32)
    ones = np.ones((g, 2, 2), np.int32)
    return a, bb, ones, b


def test_mutation_double_written_tile():
    def mut(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, o_ref, acc_ref):
        from repro.kernels.masked_matmul import jnp, pl
        kk = pl.program_id(3)

        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=jnp.float32)
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)   # EVERY k, not last

    mut.__module__ = "repro.kernels.masked_matmul"
    a, bb, ones, b = _geometry()
    vs, _ = ks.run_predicated_grouped(a, bb, ones, ones, ones,
                                      bm=b, bk=b, bn=b, kernel_fn=mut)
    assert "DOUBLE_WRITE" in codes(vs)


def test_mutation_stale_accumulator():
    def mut(out_m_ref, a_m_ref, b_m_ref, a_ref, b_ref, o_ref, acc_ref):
        from repro.kernels.masked_matmul import jnp, pl
        kk = pl.program_id(3)
        nk = pl.num_programs(3)
        # MUTATION: no k==0 zeroing — carries the previous tile's sums.
        acc_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _write():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)

    mut.__module__ = "repro.kernels.masked_matmul"
    a, bb, ones, b = _geometry()
    vs, _ = ks.run_predicated_grouped(a, bb, ones, ones, ones,
                                      bm=b, bk=b, bn=b, kernel_fn=mut)
    assert "ACC_READ_BEFORE_WRITE" in codes(vs)


def test_real_kernels_produce_reference_values():
    """The shadow run is an executable spec: its predicated output must
    equal masked dense numpy."""
    r = np.random.RandomState(1)
    g, m, k, n, b = 2, 8, 8, 8, 4
    a = r.randn(g, m, k).astype(np.float32)
    bb = r.randn(g, k, n).astype(np.float32)
    om = (r.rand(g, 2, 2) > 0.4).astype(np.int32)
    ones = np.ones((g, 2, 2), np.int32)
    vs, out = ks.run_predicated_grouped(a, bb, om, ones, ones,
                                        bm=b, bk=b, bn=b)
    assert vs == []
    ref = np.einsum("gmk,gkn->gmn", a, bb)
    mask = np.kron(om, np.ones((b, b))).astype(bool).reshape(g, m, n)
    assert np.allclose(out, np.where(mask, ref, 0.0), atol=1e-4)


# ---------------------------------------------------------------------------
# Queue overflow: the dump slot absorbs the excess
# ---------------------------------------------------------------------------

def test_queue_overflow_quarantined_on_real_kernel():
    """The REAL builder under overflow: live slots keep the reference
    prefix, the dump slot absorbs the rest, count reports the true total."""
    ii, jj, n_live = ops.build_queue(jnp.ones((4, 4), jnp.int32), capacity=5)
    assert int(n_live[0]) == 16
    assert list(np.asarray(ii)) == [0, 0, 0, 0, 1]
    assert list(np.asarray(jj)) == [0, 1, 2, 3, 0]


# ---------------------------------------------------------------------------
# Planted mutation: loose-kwarg call site → LOOSE_KWARG (lint)
# ---------------------------------------------------------------------------

def test_mutation_loose_kwarg_callsite():
    vs = lint.lint_source(
        "y = relu_matmul(x, w, compact=True, queue_builder='argsort')\n",
        path="src/repro/core/sparse_linear.py")
    assert codes(vs) == ["LOOSE_KWARG"]


def test_loose_kwargs_allowed_in_spec_construction():
    vs = lint.lint_source(
        "spec = policy.gemm_spec(dims=dims)\n"
        "p2 = SparsityPolicy(queue_builder='prefix_sum')\n"
        "p3 = p2.with_(queue_builder='argsort')\n",
        path="src/repro/core/x.py")
    assert vs == []


def test_lint_shim_call_and_ref_exemption():
    bad = lint.lint_source("out = ops.masked_matmul(a, b, m)\n",
                           path="src/repro/models/x.py")
    assert codes(bad) == ["SHIM_CALL"]
    ok = lint.lint_source("want = ref.masked_matmul(a, b, m)\n",
                          path="tests/x.py")
    assert ok == []
    # No kernels/ allowance anymore: the shims are deleted, so a bare call
    # breaks at runtime anywhere — including inside kernels/.
    in_kernels = lint.lint_source("out = masked_matmul(a, b, m)\n",
                                  path="src/repro/kernels/ops.py")
    assert codes(in_kernels) == ["SHIM_CALL"]


def test_lint_conv_fallback_and_waiver():
    bad = lint.lint_source(
        "def f(x, w):\n"
        "    return jax.lax.conv_general_dilated(x, w, (1, 1), 'SAME')\n",
        path="src/repro/models/x.py")
    assert codes(bad) == ["CONV_FALLBACK"]
    counted = lint.lint_source(
        "def f(x, w):\n"
        "    stats.record('conv:dense_fallback')\n"
        "    return jax.lax.conv_general_dilated(x, w, (1, 1), 'SAME')\n",
        path="src/repro/models/x.py")
    assert counted == []
    waived = lint.lint_source(
        "def f(x, w):\n"
        "    # dense oracle  # repro-lint: allow(CONV_FALLBACK)\n"
        "    return jax.lax.conv_general_dilated(x, w, (1, 1), 'SAME')\n",
        path="benchmarks/x.py")
    assert waived == []


def test_lint_stats_key_families():
    bad = lint.lint_source("stats.record('gemm:blocked:x')\n", path="a.py")
    assert codes(bad) == ["STATS_KEY"]
    bad2 = lint.lint_source("stats.record('bitmap:scan')\n", path="a.py")
    assert codes(bad2) == ["STATS_KEY"]
    ok = lint.lint_source(
        "stats.record('gemm:compact:4')\n"
        "stats.record('queue:prefix_sum')\n"
        "stats.record('conv:dense_fallback')\n", path="a.py")
    assert ok == []


# ---------------------------------------------------------------------------
# Instrumentation plumbing the checkers rely on
# ---------------------------------------------------------------------------

def test_gemm_event_provenance():
    with ops.collect_gemm_events() as events:
        jax.make_jaxpr(
            lambda x, w: ops.sparse_gemm(
                x, w, (jnp.ones((2, 2), jnp.int32), None),
                PALLAS_POLICY.gemm_spec(dims=(16, 16, 16)))
        )(jnp.ones((16, 16)), jnp.ones((16, 16)))
    assert [e.origin for e in events] == ["policy"]
    # origin is provenance, not identity: it must not affect spec equality.
    s1 = ops.GemmSpec(block=(8, 8, 8), schedule="compact")
    s2 = PALLAS_POLICY.gemm_spec(dims=(16, 16, 16))
    assert s1 == ops.GemmSpec(block=(8, 8, 8), schedule="compact",
                              origin="whatever")
    assert s2.origin == "policy"


def test_lifecycle_scopes_reach_the_jaxpr():
    from repro.kernels import stats

    def f(x):
        with stats.layer_scope("L0"):
            return ops.bitmap_scan(x, block=(8, 8), kind="act").sum()

    jx = jax.make_jaxpr(f)(jnp.ones((16, 16)))
    stacks = " / ".join(str(e.source_info.name_stack) for e in jx.eqns)
    assert "repro:scan:act" in stacks and "layer:L0" in stacks
