"""stream/ — streaming refactorization under value drift.

Matrix STREAMS as the first-class workload (ROADMAP item 4): a
sequence of systems with one sparsity pattern and drifting values —
Newton iterations, transient stepping, the reference's
`SamePattern_SameRowPerm` rung served continuously.  Three pieces:

  swap.py      atomic resident-factor swap — a new generation
               (factors + PackSet + warmed programs) is published in
               ONE reference assignment after validation; concurrent
               solves observe strictly old-or-new, never torn state.
  cadence.py   refine-until-degraded schedule — solves ride the
               stale factors with fresh-matrix refinement until the
               measured berr trajectory (drift lookahead included)
               says a background refactorization must start so its
               swap lands before the berr guard would trip.
  pipeline.py  the contained background worker — factors step k+1
               through the factor cache's full resilient path
               (breaker/retry/finite gate/store/fleet single-flight)
               while solves ride step k; every failure mode degrades
               to continued stale-factor serving, never an outage.
  compat.py    `scipy.sparse.linalg`-shaped `splu`/`spsolve` front,
               so transient-stepping codes adopt the pipeline
               without learning serve/.

Entry point: `SolveService.stream(a, options)` -> StreamHandle.
Drilled by tests/test_stream.py (drift + injected background
failures + the mid-swap kill site).
"""

from .cadence import Cadence
from .compat import StreamLU, splu, spsolve
from .pipeline import StreamConfig, StreamHandle
from .swap import Generation, ResidentSwap

__all__ = [
    "Cadence",
    "Generation",
    "ResidentSwap",
    "StreamConfig",
    "StreamHandle",
    "StreamLU",
    "splu",
    "spsolve",
]
