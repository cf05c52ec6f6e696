"""The least work a BATCHED numeric factorization needs: B members on
one plan are B times one member's fronts (roofline.py's counts, called
here and not copied, so the work counted is the same whatever
implements it), and every member reads its own non-zeros."""

from __future__ import annotations

import roofline


def batch_factor_flops(w, r, members: int) -> float:
    return members * roofline.factor_flops(w, r)


def batch_factor_bytes(w, r, nnz: int, itemsize: int,
                       members: int) -> float:
    return members * roofline.factor_bytes(w, r, nnz, itemsize)
