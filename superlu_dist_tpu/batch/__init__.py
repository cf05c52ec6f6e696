"""batch/ — the many-small-systems engine (ROADMAP item 2).

Static pivoting (GESP) means every matrix sharing one sparsity
pattern shares one FactorPlan and one BatchedSchedule: the numeric
factorization and the packed trisolve are pure value-array programs
with a natural leading batch axis.  This package vmaps them —

    plan = plan_share.shared_plan(a_template)
    blu  = engine.batch_factorize(plan, values)      # values (B, nnz)
    x    = engine.batch_solve(blu, b)                # b (B, n[, nrhs])

— one schedule, one warmup, B value sets, with every member pinned
bitwise equal to its per-sample execution (tests/test_batch.py), and
with `options=` every member refined to the one-system guarantee
(tests/test_batch_refine.py; both entry points are at the package
root too).
`serving.py` holds the B-ladder/warmup discipline the serve-layer
factor coalescer (serve/coalescer.py) dispatches through.
"""

from .engine import (BatchedLU, batch_factorize, batch_solve,
                     batch_solve_factor, member_factorization,
                     per_sample_factorize)
from .plan_share import (assert_same_pattern, batch_scaled_values,
                         shared_plan)
from .serving import (BATCH_LADDER, batch_ladder, bucket_for_batch,
                      pad_values, warmup_batch)

__all__ = [
    "BatchedLU", "batch_factorize", "batch_solve",
    "batch_solve_factor", "member_factorization",
    "per_sample_factorize",
    "assert_same_pattern", "batch_scaled_values", "shared_plan",
    "BATCH_LADDER", "batch_ladder", "bucket_for_batch", "pad_values",
    "warmup_batch",
]
