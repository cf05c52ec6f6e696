"""The plain reference and the data of a COMPLEX configuration: scipy
sparse in complex128, nothing of the program.  The complex twin of
reference.py, which is not edited: the same seeds and streams, the
same three comparisons, in complex arithmetic.

Everything here is the yardstick a later PR may not change: how value
sets and right-hand sides are made from `--seed`, and the comparison
that decides `correct` (componentwise backward error on moduli, error
against the manufactured solution in the 2-norm, agreement with scipy
`splu` of the complex128 matrix).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import reference
from reference import rng_for


def value_sets(a0: sp.csr_matrix, drift: dict, seed: int, count: int):
    """`count` matrices on a0's pattern: every row of the complex a0
    rescaled by a REAL factor drawn from U(low, high), as
    reference.value_sets (the same stream, so the same factors)."""
    if drift["kind"] != "row_rescale_uniform":
        raise ValueError(f"unknown value_drift kind {drift['kind']!r}")
    if not np.iscomplexobj(a0.data):
        raise ValueError("reference_z is for complex configurations")
    rng = rng_for(seed, 1)
    rows = np.diff(a0.indptr)
    out = []
    for _ in range(count):
        scale = rng.uniform(drift["low"], drift["high"], a0.shape[0])
        a = a0.copy()
        a.data = (a0.data * np.repeat(scale, rows)).astype(np.complex128)
        out.append(a)
    return out


def systems(mats, seed: int, count: int):
    """`count` manufactured systems (xtrue, b), system j on
    mats[j % len(mats)]: xtrue standard complex normal (real and
    imaginary parts each standard normal, both from the one stream
    reference.systems uses), b = A·xtrue in complex128."""
    rng = rng_for(seed, 2)
    out = []
    for j in range(count):
        a = mats[j % len(mats)]
        n = a.shape[0]
        xtrue = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        out.append((xtrue, a @ xtrue))
    return out


class Checker(reference.Checker):
    """reference.Checker in complex128: the same limits, the same
    judging (`judge`: an answer that is None, is not an answer, or
    misses a limit counts as failed; the first answers on matrix 0 are
    also held against scipy's own LU), with |A| of moduli and scipy's
    complex LU.  Built and used outside the measured window."""

    def score(self, mat_index: int, b, xtrue, x) -> dict | None:
        """berr and relerr of one answer, or None when it is not an
        answer at all: not complex, wrong shape, not finite."""
        x = np.asarray(x)
        if (x.shape != xtrue.shape or not np.iscomplexobj(x)
                or not np.isfinite(x).all()):
            return None
        a, abs_a = self.mats[mat_index], self.abs_mats[mat_index]
        xz = x.astype(np.complex128)
        denom = abs_a @ np.abs(xz) + np.abs(b)
        denom[denom == 0.0] = 1.0
        return {
            "berr": float(np.max(np.abs(b - a @ xz) / denom)),
            "relerr": float(np.linalg.norm(xz - xtrue)
                            / np.linalg.norm(xtrue)),
        }

    def vs_splu(self, mat_index: int, b, x) -> float:
        lu = self._splu.get(mat_index)
        if lu is None:
            lu = self._splu[mat_index] = spla.splu(
                self.mats[mat_index].tocsc())
        xref = lu.solve(b)
        return float(np.linalg.norm(np.asarray(x, np.complex128) - xref)
                     / np.linalg.norm(xref))
