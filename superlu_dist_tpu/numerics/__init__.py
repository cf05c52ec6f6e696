"""numerics/ — the numerical-trust layer (DESIGN.md §21).

The GESP architecture secures stability BEFORE the numeric phase and
never pivots at runtime; this package is the verification layer the
reference hedges that bet with (pdgscon / pdgsrfs, PAPER.md L5):

  errors.py   typed taxonomy of wrong-answer failure modes
              (re-exported by serve/errors.py)
  gscon.py    Hager-Higham rcond estimation riding the resident
              packed trisolve — zero extra factorizations
  ledger.py   tiny-pivot perturbations as first-class per-
              factorization data (count, locations, magnitude)
  policy.py   ConditionPolicy(serve|stamp|refuse): rcond thresholds
              feeding refusal, stamping, guard tightening and the
              escalation ladder
  gauntlet.py hard-matrix corpus + the zero-silent-wrong-answers
              drill (tests/test_numerics.py)
"""

from .errors import (
    InvalidInputError,
    NumericalError,
    SingularMatrixError,
    StructurallySingularError,
)
from .gscon import ensure_rcond, estimate_rcond, one_norm
from .ledger import (
    PerturbationLedger,
    PerturbedResult,
    build_ledger,
    stamp_perturbed,
)
from .policy import ConditionPolicy, cond_estimate_enabled

__all__ = [
    "ConditionPolicy",
    "InvalidInputError",
    "NumericalError",
    "PerturbationLedger",
    "PerturbedResult",
    "SingularMatrixError",
    "stamp_perturbed",
    "StructurallySingularError",
    "build_ledger",
    "cond_estimate_enabled",
    "ensure_rcond",
    "estimate_rcond",
    "one_norm",
]
