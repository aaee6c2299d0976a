"""Static-analysis subsystem: overflow proofs, jit lint, invariant prover.

Three passes, one CLI (``python -m repro.analysis``), one CI gate
(DESIGN.md §12):

* :mod:`repro.analysis.overflow` — abstract-interpretation proof, over the
  integer-interval domain of :mod:`repro.analysis.intervals`, that no
  intermediate of the field-arithmetic pipeline (limb GEMM, Barrett folds,
  Montgomery tables, polyeval, chunk-then-fold accumulation) exceeds
  int64 / uint64 / the f64 mantissa for ANY ``(p, scheme, s, t, λ, m, bk)``
  the autotuner can emit.  Exports :func:`~repro.analysis.overflow.
  certified_bk`, the machine-checked accumulation window the kernels
  consume.
* :mod:`repro.analysis.jitlint` — AST lint for jit-stability hazards:
  host syncs in hot paths, Python branches on traced values, positional
  ``static_argnums``, donated-buffer reuse, shape-dependent allocation in
  loops, bare ``assert``s.  ``# analysis: allow(<rule>)`` suppresses a
  site; ``analysis-baseline.json`` absorbs the audited legacy sites.
* :mod:`repro.analysis.invariants` — prover for the protocol inequalities
  (``N ≥ t²+z``, ``N ≥ t²+z+2a``, C1–C3, Theorem 1) over every
  spec-construction path, cross-validated against the Theorem-3 closed
  forms of :mod:`repro.core.worker_counts`.
"""
from .intervals import Interval
from .overflow import (
    certified_bk,
    certified_limb_k,
    verify_field_pipeline,
    verify_spec_space,
)
from .report import Finding, load_baseline, write_baseline

__all__ = [
    "Interval",
    "Finding",
    "certified_bk",
    "certified_limb_k",
    "load_baseline",
    "verify_field_pipeline",
    "verify_spec_space",
    "write_baseline",
]
