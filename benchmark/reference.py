"""The plain reference and the data: scipy sparse in float64, nothing of
the program.

Everything here is the yardstick a later PR may not change: how value
sets and right-hand sides are made from `--seed`, and the comparison
that decides `correct` (componentwise backward error, error against
the manufactured solution, agreement with scipy `splu`).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One generator per (seed, stream).  `--seed` may be any whole
    number a little over 2**31; SeedSequence wants it non-negative."""
    return np.random.default_rng([int(seed) & (2**63 - 1), int(stream)])


def value_sets(a0: sp.csr_matrix, drift: dict, seed: int, count: int):
    """`count` matrices on a0's pattern: every row of a0 rescaled by a
    factor drawn from U(low, high) — the drift of a time-stepper's
    values between steps."""
    if drift["kind"] != "row_rescale_uniform":
        raise ValueError(f"unknown value_drift kind {drift['kind']!r}")
    rng = rng_for(seed, 1)
    rows = np.diff(a0.indptr)
    out = []
    for _ in range(count):
        scale = rng.uniform(drift["low"], drift["high"], a0.shape[0])
        a = a0.copy()
        a.data = a0.data * np.repeat(scale, rows)
        out.append(a)
    return out


def systems(mats, seed: int, count: int):
    """`count` manufactured systems (xtrue, b), system j on
    mats[j % len(mats)]: xtrue standard normal, b = A·xtrue in
    float64."""
    rng = rng_for(seed, 2)
    out = []
    for j in range(count):
        a = mats[j % len(mats)]
        xtrue = rng.standard_normal(a.shape[0])
        out.append((xtrue, a @ xtrue))
    return out


class Checker:
    """Holds |A| and (lazily) scipy's own LU of each matrix, and scores
    answers.  Built and used outside the measured window."""

    def __init__(self, mats, guarantees: dict):
        self.mats = mats
        self.abs_mats = [abs(a) for a in mats]
        self._splu: dict[int, object] = {}
        self.berr_max = (guarantees["berr_max_in_eps_float64"]
                         * float(np.finfo(np.float64).eps))
        self.relerr_max = float(guarantees["relerr_max"])
        self.vs_splu_max = float(guarantees["vs_splu_max"])

    def score(self, mat_index: int, b, xtrue, x) -> dict | None:
        """berr and relerr of one answer, or None when it is not an
        answer at all (wrong shape or dtype class, not finite)."""
        x = np.asarray(x)
        if x.shape != xtrue.shape or not np.isfinite(x).all():
            return None
        a, abs_a = self.mats[mat_index], self.abs_mats[mat_index]
        x64 = x.astype(np.float64)
        denom = abs_a @ np.abs(x64) + np.abs(b)
        denom[denom == 0.0] = 1.0
        return {
            "berr": float(np.max(np.abs(b - a @ x64) / denom)),
            "relerr": float(np.linalg.norm(x64 - xtrue)
                            / np.linalg.norm(xtrue)),
        }

    def vs_splu(self, mat_index: int, b, x) -> float:
        lu = self._splu.get(mat_index)
        if lu is None:
            lu = self._splu[mat_index] = spla.splu(
                self.mats[mat_index].tocsc())
        xref = lu.solve(b)
        return float(np.linalg.norm(np.asarray(x, np.float64) - xref)
                     / np.linalg.norm(xref))

    def judge(self, answers, splu_on: int = 1) -> dict:
        """`answers`: list of (mat_index, b, xtrue, x or None).  An
        answer that is None (the operation raised or was refused), is
        not an answer, or misses a limit counts as failed.  The first
        `splu_on` answers on matrix 0 are also held against scipy's
        own LU."""
        failed = 0
        worst = {"berr": 0.0, "relerr": 0.0, "vs_splu": 0.0}
        splu_left = splu_on
        for mat_index, b, xtrue, x in answers:
            s = None if x is None else self.score(mat_index, b, xtrue, x)
            if s is None:
                failed += 1
                continue
            if mat_index == 0 and splu_left > 0:
                splu_left -= 1
                s["vs_splu"] = self.vs_splu(0, b, x)
            for k, v in s.items():
                worst[k] = max(worst[k], v)
            if (s["berr"] > self.berr_max
                    or s["relerr"] >= self.relerr_max
                    or s.get("vs_splu", 0.0) >= self.vs_splu_max):
                failed += 1
        compared = [
            {"name": "berr_max", "value": worst["berr"],
             "limit": self.berr_max},
            {"name": "relerr_max", "value": worst["relerr"],
             "limit": self.relerr_max},
            {"name": "vs_splu_max", "value": worst["vs_splu"],
             "limit": self.vs_splu_max},
        ]
        return {"attempted": len(answers), "failed": failed,
                "compared": compared,
                "splu_compared": splu_on - splu_left}
