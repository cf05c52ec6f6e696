"""The least work a numeric factorization needs, counted from the
fronts' shapes, and its share of the chip's roofline.

A front with pivot width w and r off-block rows (m = w + r) costs
2/3 w^3 (partial LU) + 2 w^2 r (two triangular solves) + 2 w r^2 (the
Schur update) floating-point operations: the multifrontal count the
program's own plan uses (plan/frontal.py front_flops), copied here so
that no later PR can move it.  Padding to bucket shapes and explicit
zeros are the program's choice and are not counted as work.

The least bytes: every front's factor panels (w*m of L with the pivot
block, r*w of U) are written once, its r x r update matrix is written
once and read once by the parent, and the matrix's own non-zeros are
read once.  Anything less needs a different algorithm.
"""

from __future__ import annotations

import numpy as np


def factor_flops(w, r) -> float:
    w = np.asarray(w, dtype=np.float64)
    r = np.asarray(r, dtype=np.float64)
    return float(np.sum(2.0 / 3.0 * w**3 + 2.0 * w * w * r
                        + 2.0 * w * r * r))


def factor_bytes(w, r, nnz: int, itemsize: int) -> float:
    w = np.asarray(w, dtype=np.float64)
    r = np.asarray(r, dtype=np.float64)
    panels = np.sum(w * (w + r) + r * w)
    updates = np.sum(2.0 * r * r)
    return float((panels + updates + nnz) * itemsize)


def roofline_share(flops: float, nbytes: float, device_s: float,
                   peaks: dict) -> tuple[float, str]:
    """(share in %, which bound): the least time the chip could take,
    the larger of flops / peak and bytes / bandwidth, over the device
    time the work really took."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / device_s, bound
