"""The least work of a COMPLEX numeric factorization, counted from the
fronts' shapes: roofline.py's count in complex arithmetic.

roofline.factor_flops counts a multiply-add as 2 floating-point
operations, which is what a real one is.  A complex multiply-add
(a + b·c on complex scalars) is 4 real multiplications and 4 real
additions, 8 operations, however it is lowered: natively, or on real
and imaginary planes as four real matrix products and two additions
(ops/pair_lu.pmatmul).  So the fronts' least flops in complex are four
times roofline.factor_flops' count; the divisions of a pivot column
are of lower order, as they are there.  roofline.py may not be edited,
and its count stays the real cells' own.

The least bytes are roofline.factor_bytes' entries at the complex
itemsize (8 for complex64: two float32 planes hold the same bytes as
one complex64 array).
"""

from __future__ import annotations

import roofline

REAL_OPS_PER_COMPLEX_MULTIPLY_ADD = 8
REAL_OPS_PER_REAL_MULTIPLY_ADD = 2


def factor_flops(w, r) -> float:
    return (REAL_OPS_PER_COMPLEX_MULTIPLY_ADD
            // REAL_OPS_PER_REAL_MULTIPLY_ADD
            * roofline.factor_flops(w, r))


def factor_bytes(w, r, nnz: int, itemsize: int) -> float:
    """`itemsize`: of the complex factor dtype (8 for complex64)."""
    return roofline.factor_bytes(w, r, nnz, itemsize)
