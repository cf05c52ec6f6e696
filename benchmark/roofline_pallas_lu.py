"""The work of the Pallas panel LU (`ops/pallas_lu.py`), counted from
the shapes of the buckets it is handed, and kept here so that no later
PR can move it.

One call takes N fronts of mb x mb and eliminates wb columns of each
in fast memory: per front 2/3 wb^3 (the pivot block) + 2 wb^2 r (the
two panels) + 2 wb r^2 (the Schur update) floating-point operations
with r = mb - wb, `roofline.factor_flops` at the bucket's shape; and it
reads every front once and writes it once.  The shapes are the
buckets', padding included: this is the kernel's efficiency on what it
is handed, not the algorithm's need (`factor_roofline` counts that, on
the fronts' own shapes).  Anything less needs another kernel.
"""

from __future__ import annotations

import roofline


def panel_lu_flops(shapes) -> float:
    """`shapes`: [[N, mb, wb], ...], one a bucket."""
    return float(sum(n * roofline.factor_flops([wb], [mb - wb])
                     for n, mb, wb in shapes))


def panel_lu_bytes(shapes, itemsize: int) -> float:
    return float(sum(2 * n * mb * mb for n, mb, _ in shapes) * itemsize)
