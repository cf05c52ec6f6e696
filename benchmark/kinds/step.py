"""Generator kind `step`: a closed loop of one caller.  Each step takes
the next of a ring of value sets made from the seed before the window,
refactors on the held plan (`factorize(plan=...)`) and solves one
right-hand side refined to the stated accuracy, with the answer on
the host.  Parameters (traffic file): ring, warmup_steps,
trace_steps."""

from __future__ import annotations

import time

import numpy as np

from reference import Checker, systems, value_sets


def _block(jax, lu):
    """Wait for a factorization's device arrays."""
    jax.block_until_ready([v for v in vars(lu.device_lu).values()
                           if isinstance(v, (jax.Array, list, tuple))])


def _step(run, state, i):
    """One time step, through the entry points a caller uses."""
    jax, slu = run.jax, run.slu
    j = i % len(state["mats"])
    st = slu.Stats()
    with run.spans.span("bench.factorize"):
        lu = slu.factorize(state["csr"][j], state["opts"],
                           plan=state["plan"], grid=state["grid"])
        _block(jax, lu)
    with run.spans.span("bench.solve"):
        x = np.asarray(slu.solve(lu, state["systems"][j][1], stats=st))
    return j, x, st.refine_steps


def setup(run) -> dict:
    slu, tr = run.slu, run.traffic
    a0 = run.matrix()
    state = {"a0": a0, "opts": run.options(), "grid": run.grid()}
    reseed(run, state, run.seed)
    with run.spans.span("bench.plan"):
        state["plan"] = slu.plan_factorization(
            slu.csr_from_scipy(a0), state["opts"])
    f = state["plan"].frontal
    run.readings["fronts"] = {"w": np.asarray(f.w), "r": np.asarray(f.r),
                              "nnz": int(a0.nnz)}
    warm(run, state)
    return state


def reseed(run, state, seed: int) -> None:
    """The ring of value sets and their systems, from the seed."""
    ring = run.traffic["ring"]
    mats = value_sets(state["a0"], run.config["value_drift"], seed, ring)
    state.update(mats=mats,
                 csr=[run.slu.csr_from_scipy(a) for a in mats],
                 systems=systems(mats, seed, ring))


def warm(run, state) -> None:
    """Every program the window drives runs here first, with the
    options in force (the control tests change them and warm again)."""
    state["opts"] = run.options()
    with run.spans.span("bench.warmup"):
        for i in range(run.traffic["warmup_steps"]):
            _step(run, state, i)
    # the spans of warm-up are set-up's, not the window's
    for name in ("bench.factorize", "bench.solve"):
        run.spans.by_name.pop(name, None)


def window(run, state) -> None:
    state.update(answers=[], walls=[], refine_steps=[])

    def one(i):
        t_step = time.perf_counter()
        try:
            j, x, steps = _step(run, state, i)
        except Exception as e:          # noqa: BLE001 — a step that
            # raises is a failed operation of the window, counted
            print(f"step {i} raised {type(e).__name__}: {e}", flush=True)
            j, x, steps = i % len(state["mats"]), None, 0
        state["walls"].append(time.perf_counter() - t_step)
        state["answers"].append((j, x))
        state["refine_steps"].append(steps)

    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < run.seconds:
        one(i)
        i += 1
    elapsed = time.perf_counter() - t0
    # a time per step over all the work and all the time of the window
    run.readings["step_s"] = elapsed / i
    run.notes["steps"] = i
    run.notes["window_s"] = elapsed
    if run.trace:
        # the traced steps follow the window, so that writing the trace
        # out (many seconds) stalls nothing that is timed; their
        # answers are checked with the window's
        n = run.traffic["trace_steps"]
        run.start_trace()
        for k in range(n):
            one(i + k)
        run.stop_trace()
        run.readings["traced_steps"] = n
    run.readings["step_walls"] = state["walls"]
    run.readings["refine_steps"] = state["refine_steps"]


def check(run, state) -> dict:
    checker = Checker(state["mats"], run.config["guarantees"])
    answers = [(j, state["systems"][j][1], state["systems"][j][0], x)
               for j, x in state["answers"]]
    return checker.judge(answers)


def close(run, state) -> None:
    pass
