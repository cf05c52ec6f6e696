"""Generator kind `bstep`: kinds/step.py's closed loop of one caller on
a BATCH of same-pattern systems.  Each step takes the next of a ring
of value stacks (members x nnz float64, one Picard iterate of every
member, made from the seed before the window), refactors every member
on the held plan (`batch_factorize(plan, values, options=...)`) and
solves one right-hand side a member refined to the stated accuracy
(`batch_solve`), with the (members, n) answer on the host.  The plan
is made once, from the member-wise mean of the first stack (GESP: one
row permutation and one pair of scalings for all).  Parameters
(traffic file): ring, warmup_steps, trace_steps.  The batch is the
configuration's `batch` (`rehearsal_batch` on the CPU).

Every answer of the window is kept and checked after it, every member
of each (reference_b.Checker), as kinds/step.py keeps its answers:
16 MB a step at 2,048 members.

A program without a refined batched solve at its root
(`batch_factorize(..., options=)` and `batch_solve`: the tree before
the PR that brought this kind) cannot run the configuration: the run
is refused before any set-up, with a code other than 0 and no result
line, as where there is no TPU.  Its batched solve is unrefined and
sweeps the members one after another."""

from __future__ import annotations

import inspect

import numpy as np
import scipy.sparse as sp

import harness
import reference_b


def _generator(run):
    m = run.config["matrix"]
    gen = harness.load_module("gen_" + m["generator"], "configs",
                              "gen_" + m["generator"] + ".py")
    args = {k: m["args"][k] for k in ("npar", "nperp", "vmax")}
    return gen, gen.grid(**args)


# the timed loop is kinds/step.py's own (`warm`, `window`: one more
# instance of that module, as kinds/zstep.py takes one), on this
# kind's `_step`; the ring is `state["mats"]` there
_loop = harness.load_module("kind_step_for_bstep", "kinds", "step.py")


def members(run) -> int:
    return int(run.config["rehearsal_batch" if run.rehearse
                          else "batch"])


def _step(run, state, i):
    """One step, through the entry points a caller uses."""
    jax, slu = run.jax, run.slu
    j = i % len(state["mats"])
    st = slu.Stats()
    with run.spans.span("bench.factorize"):
        blu = slu.batch_factorize(state["plan"], state["mats"][j],
                                  options=state["opts"])
        jax.block_until_ready((blu.panels, blu.packs))
    with run.spans.span("bench.solve"):
        x = np.asarray(slu.batch_solve(blu, state["systems"][j][1],
                                       stats=st))
    # (members live, members swept) of every refinement pass
    state.setdefault("passes", []).append(
        [list(p) for p in (st.batch or {}).get("passes", ())])
    return j, x, st.refine_steps


_loop._step = _step


def setup(run) -> dict:
    slu = run.slu
    factor = getattr(slu, "batch_factorize", None)
    if (factor is None or not hasattr(slu, "batch_solve")
            or "options" not in inspect.signature(factor).parameters):
        raise harness.Refused(
            "this program has no refined batched solve "
            "(`batch_factorize(plan, values, options=)` and "
            "`batch_solve` at the package root): it cannot run the "
            "configuration " + run.config["name"])
    a0 = run.matrix()
    gen, g = _generator(run)
    state = {"a0": a0, "gen": gen, "g": g, "opts": run.options(),
             "block": reference_b.BlockDiagonal(
                 g["indptr"], g["indices"], g["n"], members(run))}
    reseed(run, state, run.seed)
    mean = sp.csr_matrix((state["mats"][0].mean(axis=0), g["indices"],
                          g["indptr"]), shape=a0.shape)
    with run.spans.span("bench.plan"):
        state["plan"] = slu.plan_factorization(
            slu.csr_from_scipy(mean), state["opts"])
    f = state["plan"].frontal
    run.readings["fronts"] = {"w": np.asarray(f.w), "r": np.asarray(f.r),
                              "nnz": int(a0.nnz)}
    run.readings["batch_members"] = members(run)
    warm(run, state)
    return state


def reseed(run, state, seed: int) -> None:
    """The ring of value stacks and their systems, from the seed."""
    sets = reference_b.value_sets(
        state["gen"], state["g"], run.config["model"], seed,
        members(run), run.traffic["ring"])
    state.update(mats=sets,
                 systems=reference_b.systems(state["block"], sets, seed))


warm = _loop.warm
window = _loop.window


def check(run, state) -> dict:
    m = run.config["matrix"]["args"]
    checker = reference_b.Checker(
        state["block"], state["g"], state["mats"],
        run.config["guarantees"], run.seed, bandwidth=m["npar"] + 1)
    answers = [(j, state["systems"][j][1], state["systems"][j][0], x)
               for j, x in state["answers"]]
    verdict = checker.judge(answers)
    run.notes["members"] = members(run)
    passes = state["passes"][-len(answers):]
    run.notes["refine_passes"] = {
        "fewest": min(map(len, passes)), "most": max(map(len, passes)),
        "mean": sum(map(len, passes)) / len(passes),
        "last_step": passes[-1]}
    # where a step's seconds went, the window's steps alone: which
    # of the two calls carries a spread between runs
    for name in ("bench.factorize", "bench.solve"):
        v = sorted(run.spans.by_name.get(name, [0.0])[:run.notes["steps"]])
        run.notes[name + "_s"] = {
            "mean": sum(v) / len(v), "median": v[len(v) // 2],
            "p90": v[(9 * len(v)) // 10]}
    run.notes["members_failed"] = verdict["members_failed"]
    return verdict


def close(run, state) -> None:
    pass
