"""Plan sharing: the contract that makes batching legal.

A batch is B matrices with IDENTICAL sparsity pattern and one
FactorPlan between them (the SamePattern_SameRowPerm rung of the Fact
reuse ladder, applied B-wide).  Sharing the plan means sharing the
row/column permutations AND the equilibration scalings of the
template matrix — GESP semantics: the pivot order was chosen for the
template's values, and siblings inherit it.  That is exactly the
regime the engine targets (ensembles, parameter sweeps, per-user
models drifting around one operating point); a member whose values
stray far enough that the template's pivots go bad reports through
the tiny-pivot ledger / nzero refusal, not silently (DESIGN.md §26).
"""

from __future__ import annotations

import numpy as np

from ..options import Options
from ..plan.plan import FactorPlan, pattern_sha1, plan_factorization
from ..sparse import CSRMatrix


def shared_plan(a: CSRMatrix, options: Options | None = None,
                stats=None) -> FactorPlan:
    """The once-per-pattern plan every batch member rides — a thin
    alias of plan_factorization, named for the contract: ONE plan, B
    value sets."""
    return plan_factorization(a, options, stats=stats)


def assert_same_pattern(plan: FactorPlan, a: CSRMatrix) -> None:
    """Refuse a member whose pattern differs from the plan's (typed,
    before any numeric work — the earliest-provable-layer
    discipline).  O(nnz) exact compare: the COO order the plan's
    assembly maps were built against IS the membership test."""
    rows, cols, _ = a.to_coo()
    if (a.n != plan.n or len(rows) != len(plan.coo_rows)
            or not np.array_equal(rows, plan.coo_rows)
            or not np.array_equal(cols, plan.coo_cols)):
        raise ValueError(
            "batch member pattern differs from the shared plan "
            f"(n={a.n} vs {plan.n}, nnz={len(rows)} vs "
            f"{len(plan.coo_rows)}); same-pattern membership is the "
            "batching contract — plan the new pattern separately")


def batch_scaled_values(plan: FactorPlan,
                        values: np.ndarray) -> np.ndarray:
    """Dr·A·Dc applied to a (B, nnz) stack of value arrays in the
    plan's COO order — the batched twin of plan.scaled_values.  The
    two-step multiply order (row scale, THEN column scale) replays
    the per-sample expression exactly, so each row is bitwise equal
    to plan.scaled_values of that member (elementwise broadcasting
    over a leading axis changes nothing per lane).  The float64
    ORACLE since PR 48: `batch_factorize` scales in its factor
    program, in the factor dtype, and the tests hold that to this
    (bitwise for float64 factors, 4 ulp for float32)."""
    values = np.asarray(values)
    if values.ndim != 2 or values.shape[1] != len(plan.coo_rows):
        raise ValueError(
            f"values must be (B, nnz={len(plan.coo_rows)}); got "
            f"{values.shape}")
    rs = plan.row_scale[plan.coo_rows]
    cs = plan.col_scale[plan.coo_cols]
    return (values * rs[None, :]) * cs[None, :]


def batch_key(a: CSRMatrix) -> str:
    """Pattern fingerprint the coalescer buckets same-pattern factor
    requests by (serve/coalescer.py)."""
    return pattern_sha1(a)
