"""Static bitmap-contract verifier (docs/static_analysis.md).

Three checkers, no kernel execution anywhere:

  * ``jaxpr_audit``      — lifecycle proof over traced training steps
  * ``kernel_sanitizer`` — shadow-memory re-execution of the Pallas kernels
  * ``lint``             — AST rules pinning the spec-driven GEMM API

``python -m repro.analysis --fail-on-violation`` runs all three (the CI
gate); ``benchmarks/kernel_audit.py`` renders the same rows as a table.
"""
from .jaxpr_audit import WORKLOADS, audit_fn, audit_jaxpr, audit_workloads
from .kernel_sanitizer import (
    run_compact_grouped,
    run_predicated_grouped,
    sanitize_all,
)
from .lint import lint_paths, lint_source
from .report import Violation, format_table, to_csv, to_json

__all__ = [
    "Violation",
    "WORKLOADS",
    "audit_fn",
    "audit_jaxpr",
    "audit_workloads",
    "format_table",
    "lint_paths",
    "lint_source",
    "run_compact_grouped",
    "run_predicated_grouped",
    "sanitize_all",
    "to_csv",
    "to_json",
]
