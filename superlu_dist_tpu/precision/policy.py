"""Precision policy: which dtype factors, which dtype solves, how the
refinement residual is accumulated — and when to climb.

The reference ships mixed precision as a dedicated expert driver
(`psgssvx_d2`, SRC/psgssvx_d2.c:516: factor in single, refine with a
double residual) and leaves the "what if single wasn't enough" decision
to the caller.  Here the whole strategy is ONE value object threaded
through every numeric phase and the serve layer:

    PrecisionPolicy(factor_dtype, solve_dtype, residual, target_dtype)

  * `factor_dtype` — the numeric factorization's precision (an
    Options.FACTOR_KEY_FIELDS member: it changes what factors are
    computed, so it re-keys the serve factor cache).
  * `solve_dtype`  — an explicit pin of the dtype client right-hand
    sides are cast to before the solve (a solve-time knob that
    downcasts client buffers; None leaves them as sent).  It is NOT
    the sweeps' operand dtype: that is always the factor's precision
    (`sweep_operand_dtype`, below), whatever this field says.
  * `residual`     — how `r = b − A·x` is accumulated during
    refinement: PLAIN (working precision), DOUBLEWORD (two-float df64
    fp32 pairs, zero fp64 ops in the jitted path —
    precision/doubleword.py), or FP64 (native refine_dtype
    accumulation: exact on CPU, EMULATED AND SLOW on TPU).
  * `target_dtype` — the accuracy class the caller is buying
    (Options.refine_dtype: the eps the refinement loop drives berr
    to, and the ceiling of the escalation ladder).

The LADDER is the adaptive part: bf16 → fp32+df64-IR → fp64.  A rung's
refinement contract (cond(A)·eps_factor < 1, SURVEY.md §2.6) is watched
at runtime by obs/health — berr plateauing above the target class, the
refine loop stalling, pivot growth beyond 1/eps_factor — and
`classify_trigger` turns those signals into the decision (and the
health-event label) to re-factor at `next_factor_dtype`.  models/gssvx
walks the ladder automatically; the serve layer uses the same rung
relation for dtype-TIER serving (a resident fp32 factor serves an
fp64-accuracy request through df64 refinement instead of paying a cold
fp64 factorization, serve/service.py).

Host/device split for DOUBLEWORD (important, also in DESIGN.md §13):
doubleword is a LOWERING strategy for accelerators without fast fp64.
The host refinement loop (models/refine.py) satisfies the same
"residual carries ≥2× factor precision" contract with native numpy
float64 — on CPU that is the faster AND more accurate implementation —
while the jitted device loop (ops/batched.make_fused_solver) uses the
fp32-pair kernels and converges to DF64_EPS.  Both stop in the same
eps-class ladder; neither path ever silently degrades the other's.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import numpy as np

from .. import flags
from ..options import IterRefine, Options


class ResidualMode(enum.Enum):
    """Refinement-residual accumulation strategy (Options.residual_mode
    carries the string value; "auto" at the Options layer resolves to
    PLAIN or FP64 from iter_refine for backward compatibility)."""

    PLAIN = "plain"             # working (factor) precision
    DOUBLEWORD = "doubleword"   # two-float fp32 df64 (device-native)
    FP64 = "fp64"               # native refine_dtype accumulation


RESIDUAL_MODES = ("auto",) + tuple(m.value for m in ResidualMode)


def resolve_residual_mode(options: Options) -> str:
    """The ONE resolution of Options.residual_mode="auto": the
    pre-policy behavior — SLU_SINGLE accumulated in working precision
    (PLAIN), everything else in refine_dtype (FP64).  models/refine.py
    and ops/batched.make_fused_solver both resolve through here so the
    host and device loops cannot disagree."""
    mode = getattr(options, "residual_mode", "auto") or "auto"
    if mode not in RESIDUAL_MODES:
        raise ValueError(
            f"unknown residual_mode {mode!r}; expected one of "
            f"{RESIDUAL_MODES}")
    if mode != "auto":
        return mode
    return (ResidualMode.PLAIN.value
            if options.iter_refine == IterRefine.SLU_SINGLE
            else ResidualMode.FP64.value)


def sweep_operand_dtype(factor_dtype, operand_dtype) -> np.dtype:
    """The ONE rule for the dtype a triangular sweep's operand is cast
    to, called by the host loop (models/gssvx.solve, for x0's
    right-hand side and every refinement correction's residual) and by
    the device loop (ops/batched.make_fused_solver): the FACTOR's
    precision — psgsrfs_d2's scheme (SRC/psgsrfs_d2.c:229: residual in
    double, the correction solved in single).  Realness is the
    system's: a complex operand on real factors takes the complex
    dtype of the factor's width (complex64 for bfloat16, which has no
    complex twin).  The residual, berr and the accumulation of the
    answer stay in the refine dtype, outside this rule."""
    fdt = np.dtype(factor_dtype)
    if np.dtype(operand_dtype).kind == "c" and fdt.kind != "c":
        return np.promote_types(fdt, np.complex64)
    return fdt


def refine_eps(refine_dtype) -> float:
    """The berr at which a refinement loop stops: the ONE threshold of
    the host loop (models/refine.iterative_refine) and of the fused
    device loops (ops/batched.make_fused_solver), the rounding unit of
    a product in the accumulator's arithmetic.

    A real accumulator: eps(refine_dtype), the reference's class
    (pdgsrfs.c: berr <= eps ends the loop).  A complex one: sqrt(2)
    times the eps of the real dtype of the same width, because a
    complex product rounds by up to sqrt(2)·gamma_2 = 2·sqrt(2)·u,
    u = eps/2 (Higham, Accuracy and Stability of Numerical
    Algorithms, Lemma 3.5), where a real one rounds by u.  What it
    decides: the berr of a complex128 answer whose last correction
    has arrived stands at 0.97-1.43 eps(float64) (61 value sets of
    ex11 -n 256 on the chip, PERF.md section 6, PR 44), astride the
    real threshold, so whether the loop paid one more sweep, which
    gains nothing, fell by value set: three passes or four, 0.07 s
    apart on the 2x2 grid."""
    rdt = np.dtype(refine_dtype)
    if rdt.kind == "c":
        return float(np.finfo(rdt.char.lower()).eps) * 2.0 ** 0.5
    return float(np.finfo(rdt).eps)


def _eps(dtype_name: str) -> float:
    """eps of a dtype name; jnp.finfo understands the ml_dtypes
    families (bfloat16) that numpy's doesn't."""
    import jax.numpy as jnp
    return float(jnp.finfo(jnp.dtype(dtype_name)).eps)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """One precision strategy, applied to Options via `apply()`."""

    factor_dtype: str = "float32"
    solve_dtype: Optional[str] = None      # None: client rhs as sent
    residual: ResidualMode = ResidualMode.DOUBLEWORD
    target_dtype: str = "float64"          # the accuracy class sold

    def __post_init__(self):
        _eps(self.factor_dtype)            # raise early on a typo
        _eps(self.target_dtype)
        if self.solve_dtype is not None:
            _eps(self.solve_dtype)
        if not isinstance(self.residual, ResidualMode):
            object.__setattr__(self, "residual",
                               ResidualMode(self.residual))

    def apply(self, options: Options | None = None) -> Options:
        """Options with this policy installed.  PLAIN maps to the
        SLU_SINGLE refinement rung, the extended-precision modes to
        SLU_DOUBLE (a caller that wants NOREFINE simply doesn't route
        its options through a policy)."""
        options = options or Options()
        return options.replace(
            factor_dtype=self.factor_dtype,
            solve_dtype=self.solve_dtype,
            residual_mode=self.residual.value,
            refine_dtype=self.target_dtype,
            iter_refine=(IterRefine.SLU_SINGLE
                         if self.residual == ResidualMode.PLAIN
                         else IterRefine.SLU_DOUBLE))

    @classmethod
    def from_options(cls, options: Options) -> "PrecisionPolicy":
        return cls(factor_dtype=options.factor_dtype,
                   solve_dtype=getattr(options, "solve_dtype", None),
                   residual=ResidualMode(
                       resolve_residual_mode(options)),
                   target_dtype=options.refine_dtype)


# -- the escalation ladder -------------------------------------------

_DEFAULT_LADDER = ("bfloat16", "float32", "float64")


def ladder() -> tuple:
    """Factor-dtype rungs, coarse → fine.  SLU_PREC_LADDER overrides
    (comma list of dtype names); entries are validated and sorted by
    decreasing eps so a shuffled override still climbs correctly."""
    raw = flags.env_str("SLU_PREC_LADDER")
    names = tuple(s.strip() for s in raw.split(",") if s.strip()) \
        or _DEFAULT_LADDER
    return tuple(sorted(names, key=_eps, reverse=True))


def ladder_policies(target_dtype: str = "float64") -> tuple:
    """The rungs as full policies: every rung below the target refines
    through the doubleword residual (the TPU-native regime), the
    target rung itself accumulates plainly (nothing finer exists to
    borrow precision from)."""
    te = _eps(target_dtype)
    out = []
    for d in ladder():
        if _eps(d) < te:
            continue                     # finer than the target: moot
        out.append(PrecisionPolicy(
            factor_dtype=d,
            residual=(ResidualMode.PLAIN if _eps(d) <= te
                      else ResidualMode.DOUBLEWORD),
            target_dtype=target_dtype))
    return tuple(out)


def next_factor_dtype(current: str,
                      ceiling: str = "float64") -> Optional[str]:
    """The next rung UP from `current` (one step, not a jump to the
    top): the coarsest ladder dtype strictly finer than `current` and
    no finer than `ceiling` (the refine/target dtype — factoring finer
    than the accuracy class being sold buys nothing).  None at the
    top.  A `current` that is not a ladder member (e.g. float16 via
    user options) still climbs by eps comparison; a ceiling finer than
    every ladder rung escalates directly to the ceiling — the
    pre-ladder single-shot behavior, kept as the safety net."""
    cur_e, ceil_e = _eps(current), _eps(ceiling)
    if cur_e <= ceil_e:
        return None                      # already at/above the target
    best = None
    for d in ladder():
        e = _eps(d)
        if e < cur_e and e >= ceil_e:
            if best is None or e > _eps(best):
                best = d
    return best if best is not None else ceiling


def lower_rungs(target_dtype: str) -> tuple:
    """Ladder rungs strictly COARSER than `target_dtype`, finest
    first — the probe order for serve dtype-TIER lookups (a resident
    fp32 factorization beats a resident bf16 one for serving an fp64
    request, and both beat a cold fp64 factorization)."""
    te = _eps(target_dtype)
    return tuple(sorted((d for d in ladder() if _eps(d) > te),
                        key=_eps))


# -- health-signal classification ------------------------------------

# pivot growth beyond 1/(16·eps_factor) means the GESP factorization
# amplified entries to within 4 bits of total significand loss — the
# diagnostic the reference computes offline via pdGetDiagU and this
# build watches at runtime (obs/health.pivot_growth)
_PIVOT_GROWTH_SLACK = 1.0 / 16.0


def classify_trigger(berr: float, *, stalled: bool = False,
                     pivot_growth: Optional[float] = None,
                     factor_eps: Optional[float] = None) -> str:
    """Name the health signal that justified an escalation the caller
    has already decided on (models/gssvx._escalation_core holds the
    berr class gate; this orders the EXPLANATION).  The label feeds
    obs.HEALTH.record_escalation(trigger=...) and the serve metrics —
    monitoring reads it to distinguish 'overflowed factor' from
    'conditioning ate the rung'."""
    if not np.isfinite(berr):
        return "nonfinite"
    if (pivot_growth is not None and factor_eps
            and pivot_growth * factor_eps > _PIVOT_GROWTH_SLACK):
        return "pivot_growth"
    if stalled:
        return "refine_stalled"
    return "berr_plateau"
