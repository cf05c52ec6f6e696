"""B-ladder serving discipline for the batch engine.

The bucket economics of serve/batcher.py, applied to the BATCH axis
instead of the RHS axis: batch sizes quantize up a fixed ladder
(default 1/4/8/16/32) so the compiled-program population is bounded
and warmup can compile every rung up front — zero recompiles in
steady state, whatever batch sizes traffic produces.  Short batches
pad by REPLICATING a live member (never zeros: a zero matrix is
singular, and a padded lane that trips the tiny-pivot/nzero counters
would pollute the batch's health accounting; a replicated lane is
bitwise the live lane, and its outputs are simply dropped on
fan-out).
"""

from __future__ import annotations

import numpy as np

from .. import flags
from ..options import Options
from .engine import batch_factorize, batch_solve
from .plan_share import shared_plan

BATCH_LADDER = (1, 4, 8, 16, 32)


def batch_ladder() -> tuple:
    """The active B-ladder: SLU_BATCH_LADDER (comma ints, ascending)
    or the default 1/4/8/16/32."""
    raw = flags.env_opt("SLU_BATCH_LADDER")
    if not raw:
        return BATCH_LADDER
    try:
        rungs = tuple(sorted({int(x) for x in raw.split(",")
                              if x.strip()}))
    except ValueError:
        return BATCH_LADDER
    return rungs if rungs and all(r > 0 for r in rungs) \
        else BATCH_LADDER


def bucket_for_batch(bsize: int, ladder: tuple | None = None) -> int:
    """Smallest ladder rung >= bsize (serve/batcher.bucket_for's
    discipline on the batch axis); the top rung caps it — callers
    split oversize batches into top-rung chunks."""
    ladder = ladder or batch_ladder()
    for rung in ladder:
        if bsize <= rung:
            return rung
    return ladder[-1]


def pad_values(values: np.ndarray, bucket: int) -> np.ndarray:
    """Pad a (B, nnz) value stack to the bucket rung by replicating
    member 0 — a live, factorizable lane (see module docstring); the
    caller drops rows past the true B on fan-out."""
    values = np.asarray(values)
    B = values.shape[0]
    if B >= bucket:
        return values
    fill = np.broadcast_to(values[0], (bucket - B,) + values.shape[1:])
    return np.concatenate([values, fill], axis=0)


def warmup_batch(plan, values1: np.ndarray, dtype=np.float64,
                 ladder: tuple | None = None, nrhs: int = 1) -> int:
    """Compile every ladder rung's factor AND solve programs from one
    representative value set (the unbatched arm's warmup discipline,
    per rung): after this, dispatches at any batch size quantized to
    the ladder hit compiled programs — the zero-recompile contract
    the coalescer gates on.  Returns the number
    of rungs warmed."""
    values1 = np.asarray(values1).reshape(1, -1)
    ladder = ladder or batch_ladder()
    n = plan.n
    for rung in ladder:
        blu = batch_factorize(plan, pad_values(values1, rung),
                              dtype=dtype)
        b = np.zeros((rung, n) if nrhs == 1 else (rung, n, nrhs),
                     np.float64)
        batch_solve(blu, b)
    return len(ladder)


def warmup_batch_for(a, options: Options | None = None,
                     dtype=np.float64,
                     ladder: tuple | None = None):
    """Plan a template matrix and warm the full ladder against it —
    the coalescer's prefactor-time entry point.  Returns the shared
    plan (so the caller reuses it for live dispatches)."""
    plan = shared_plan(a, options)
    warmup_batch(plan, a.data, dtype=dtype, ladder=ladder)
    return plan
