"""The plain reference and the data of a BATCH configuration: B
same-pattern systems a step, scipy and numpy in float64, nothing of
the program.  The batch twin of reference.py, which is not edited.

Everything here is the yardstick a later PR may not change: how a
batch's members, their value sets (one a Picard iterate) and their
right-hand sides are made from `--seed`, and the comparison that
decides `correct`: for EVERY member of every answer the componentwise
backward error and the error against the manufactured solution, in
block-diagonal float64 products, and for a seeded sample of members
of the first value set agreement with LAPACK's banded direct solve
(`scipy.linalg.solve_banded`, `gbsv`): the solver the configuration's
source names as the incumbent.
"""

from __future__ import annotations

import numpy as np
import scipy.linalg
import scipy.sparse as sp

from reference import rng_for


def member_params(model: dict, seed: int, members: int) -> dict:
    """The batch's members from the seed: (density, temperature, flow,
    dtnu0), each (members,).  Two populations, half the batch each:
    the first `members // 2` "ion-like", the rest "electron-like",
    whose dtnu0 is larger by the root of the mass ratio."""
    rng = rng_for(seed, 1)

    def draw(key):
        lo, hi = model[key]
        return rng.uniform(lo, hi, members)

    dtnu0 = np.full(members, float(model["dtnu0_ion"]))
    dtnu0[members // 2:] *= float(model["mass_ratio"]) ** 0.5
    return {"density": draw("density"),
            "temperature": draw("temperature"),
            "flow": draw("flow"), "dtnu0": dtnu0}


def value_sets(gen, g, model: dict, seed: int, members: int,
               count: int):
    """`count` value stacks, each (members, nnz) float64 on the
    generator's pattern: stack k is the batch's k-th Picard iterate,
    every member's density, temperature and flow moved from the
    iterate before by a seeded relative step of `picard_step`
    (standard normal times it; the flow by that share of the thermal
    speed)."""
    p = member_params(model, seed, members)
    rng = rng_for(seed, 3)
    step = float(model["picard_step"])
    out = []
    for _ in range(count):
        out.append(gen.values(g, p["density"], p["temperature"],
                              p["flow"], p["dtnu0"]))
        p = dict(
            p,
            density=p["density"] * (1.0 + step * rng.standard_normal(
                members)),
            temperature=p["temperature"] * (
                1.0 + step * rng.standard_normal(members)),
            flow=p["flow"] + step * np.sqrt(p["temperature"])
            * rng.standard_normal(members))
    return out


class BlockDiagonal:
    """`members` matrices on one CSR pattern as ONE block-diagonal
    scipy CSR whose `data` is a value stack, flattened: the pattern is
    built once, a product is one call."""

    def __init__(self, indptr, indices, n: int, members: int):
        nnz = len(indices)
        self.n, self.members = n, members
        idt = np.int32 if nnz * members < 2 ** 31 else np.int64
        self.indptr = np.concatenate(
            [[0], (np.asarray(indptr[1:], dtype=np.int64)[None, :]
                   + nnz * np.arange(members)[:, None]).ravel()]
        ).astype(idt)
        self.indices = (np.asarray(indices, dtype=np.int64)[None, :]
                        + n * np.arange(members)[:, None]
                        ).ravel().astype(idt)

    def product(self, vals, x):
        """A_m x_m for every member: vals (members, nnz), x
        (members, n) -> (members, n)."""
        size = self.n * self.members
        a = sp.csr_matrix((np.ascontiguousarray(vals).ravel(),
                           self.indices, self.indptr),
                          shape=(size, size))
        return (a @ np.ascontiguousarray(x).ravel()).reshape(x.shape)


def systems(block: BlockDiagonal, sets, seed: int):
    """One manufactured system a value stack: (xtrue, b), each
    (members, n); xtrue standard normal, b_m = A_m xtrue_m in
    float64."""
    rng = rng_for(seed, 2)
    out = []
    for vals in sets:
        xtrue = rng.standard_normal((block.members, block.n))
        out.append((xtrue, block.product(vals, xtrue)))
    return out


def banded_solve(indptr, indices, vals, b, bandwidth: int):
    """One member by LAPACK's banded direct solver (`gbsv` through
    scipy.linalg.solve_banded), float64."""
    n = len(indptr) - 1
    rows = np.repeat(np.arange(n), np.diff(indptr))
    ab = np.zeros((2 * bandwidth + 1, n))
    ab[bandwidth + rows - indices, indices] = vals
    return scipy.linalg.solve_banded((bandwidth, bandwidth), ab, b)


class Checker:
    """Scores a window's answers, member by member.  Built and used
    outside the measured window."""

    def __init__(self, block: BlockDiagonal, g, sets, guarantees: dict,
                 seed: int, bandwidth: int):
        self.block, self.g, self.sets = block, g, sets
        self.abs_sets = [np.abs(v) for v in sets]
        self.bandwidth = bandwidth
        self.berr_max = (guarantees["berr_max_in_eps_float64"]
                         * float(np.finfo(np.float64).eps))
        self.relerr_max = float(guarantees["relerr_max"])
        self.vs_banded_max = float(guarantees["vs_banded_max"])
        self.sample = np.sort(rng_for(seed, 4).choice(
            block.members, size=min(64, block.members), replace=False))

    def score(self, set_index: int, b, xtrue, x) -> dict | None:
        """(berr, relerr), each (members,), of one answer; None when
        it is not an answer at all (wrong shape or dtype class).  A
        member whose answer is not finite scores NaN: it fails, and
        it is left out of the worst figures."""
        x = np.asarray(x)
        if x.shape != xtrue.shape or x.dtype.kind != "f":
            return None
        x64 = x.astype(np.float64)
        finite = np.isfinite(x64).all(axis=1)
        x64 = np.where(finite[:, None], x64, 0.0)
        r = b - self.block.product(self.sets[set_index], x64)
        denom = self.block.product(self.abs_sets[set_index],
                                   np.abs(x64)) + np.abs(b)
        denom[denom == 0.0] = 1.0
        berr = np.max(np.abs(r) / denom, axis=1)
        relerr = (np.linalg.norm(x64 - xtrue, axis=1)
                  / np.linalg.norm(xtrue, axis=1))
        berr[~finite] = relerr[~finite] = np.nan
        return {"berr": berr, "relerr": relerr}

    def vs_banded(self, set_index: int, b, x) -> np.ndarray:
        out = []
        for m in self.sample:
            xref = banded_solve(self.g["indptr"], self.g["indices"],
                                self.sets[set_index][m], b[m],
                                self.bandwidth)
            out.append(np.linalg.norm(np.asarray(x[m], np.float64)
                                      - xref) / np.linalg.norm(xref))
        return np.asarray(out)

    def judge(self, answers) -> dict:
        """`answers`: list of (set_index, b, xtrue, x or None), one a
        step.  A step whose answer is None (it raised), is not an
        answer, or holds ONE member that misses a limit counts as
        failed.  The first answer on value set 0 is also held against
        the banded solver on the sampled members."""
        failed = members_failed = 0
        worst = {"berr": 0.0, "relerr": 0.0, "vs_banded": 0.0}
        banded_left = 1
        for set_index, b, xtrue, x in answers:
            s = None if x is None else self.score(set_index, b, xtrue, x)
            if s is None:
                failed += 1
                continue
            bad = ~((s["berr"] <= self.berr_max)
                    & (s["relerr"] < self.relerr_max))
            if set_index == 0 and banded_left > 0:
                banded_left -= 1
                s["vs_banded"] = self.vs_banded(0, b, x)
                bad[self.sample] |= ~(s["vs_banded"]
                                      < self.vs_banded_max)
            for k, v in s.items():
                if np.isfinite(v).any():
                    worst[k] = max(worst[k], float(np.nanmax(v)))
            members_failed += int(bad.sum())
            failed += bool(bad.any())
        compared = [
            {"name": "berr_max", "value": worst["berr"],
             "limit": self.berr_max},
            {"name": "relerr_max", "value": worst["relerr"],
             "limit": self.relerr_max},
            {"name": "vs_banded_max", "value": worst["vs_banded"],
             "limit": self.vs_banded_max},
        ]
        return {"attempted": len(answers), "failed": failed,
                "compared": compared, "members_failed": members_failed,
                "splu_compared": 1 - banded_left}
