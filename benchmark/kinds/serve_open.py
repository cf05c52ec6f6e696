"""Generator kind `serve_open`: an open loop over `SolveService`.  One
pattern is prefactored in set-up; every request is one right-hand side
from a pool made from the seed; arrivals follow a fixed set of
exponential gaps (drawn once from `gap_seed`, scaled to the window) in
an order drawn from `--seed`, so every seed offers the same work at the
same rate.  Each request is timed from the instant it was due to its
answer on the host.  Parameters (traffic file): ladder, rate_per_s,
gap_seed, pool, drain_timeout_s, trace_seconds."""

from __future__ import annotations

import time

import numpy as np

from harness import percentile
from reference import Checker, rng_for, systems


def arrivals(rate_per_s: float, seconds: float, gap_seed: int,
             seed: int) -> np.ndarray:
    """Due times in [0, seconds): round(rate*seconds) of them."""
    n = max(1, int(round(rate_per_s * seconds)))
    gaps = np.random.default_rng(gap_seed).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    order = rng_for(seed, 3).permutation(n)
    due = np.cumsum(gaps[order])
    return due - due[0]


def setup(run) -> dict:
    slu, tr = run.slu, run.traffic
    from superlu_dist_tpu.serve import ServeConfig, SolveService
    if run.config.get("grid"):
        raise ValueError("kind `serve_open` serves from one chip")
    a0 = run.matrix()
    state = {"mats": [a0], "csr": slu.csr_from_scipy(a0),
             "svc": SolveService(ServeConfig(ladder=tuple(tr["ladder"])))}
    reseed(run, state, run.seed)
    warm(run, state)
    return state


def reseed(run, state, seed: int) -> None:
    """The pool of right-hand sides, from the seed."""
    state["pool"] = systems(state["mats"], seed, run.traffic["pool"])


def warm(run, state) -> None:
    """prefactor() plans, factorizes and compiles every ladder width
    for the options in force: all the warm-up the product offers.  No
    request is sent before the window, so the service's histograms
    hold the window's requests and nothing else."""
    with run.spans.span("bench.prefactor"):
        state["key"] = state["svc"].prefactor(state["csr"],
                                              run.options())


def offer(run, state, due: np.ndarray, trace_from: float | None):
    """Send request i at due[i] (seconds after the start), with the
    profiler on from `trace_from` seconds; returns the start instant,
    the futures, and the send and done instants."""
    svc, key = state["svc"], state["key"]
    pool = state["pool"]
    n = len(due)
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    futs: list = [None] * n

    def on_done(i):
        def cb(_f):
            done[i] = time.perf_counter()
        return cb

    refused: list = []
    t0 = time.perf_counter()
    for i in range(n):
        if trace_from is not None and due[i] >= trace_from:
            run.start_trace()
            trace_from = None
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            with run.spans.span("bench.sleep"):
                time.sleep(wait)
        sent[i] = time.perf_counter()
        try:
            futs[i] = svc.submit(key, pool[i % len(pool)][1])
            futs[i].add_done_callback(on_done(i))
        except Exception as e:          # noqa: BLE001 — refused at the
            # door: a failed request of the window, counted
            refused.append(f"{type(e).__name__}: {e}")
    if refused:
        print(f"{len(refused)} requests refused at the door; the "
              f"first: {refused[0]}", flush=True)
    return t0, futs, sent, done


def window(run, state) -> None:
    tr = run.traffic
    due = arrivals(tr["rate_per_s"], run.seconds, tr["gap_seed"], run.seed)
    # a traced run records the window's last seconds, and stops the
    # profiler once the queue has drained: writing the trace out takes
    # many seconds and would stall the open loop
    t0, futs, sent, done = offer(
        run, state, due,
        run.seconds - tr["trace_seconds"] if run.trace else None)
    answers, errors = [], []
    deadline = time.perf_counter() + tr["drain_timeout_s"]
    for i, f in enumerate(futs):
        x = None
        if f is not None:
            try:
                x = f.result(timeout=max(0.0, deadline
                                         - time.perf_counter()))
            except Exception as e:      # noqa: BLE001 — a failed
                # request of the window (time-outs too), counted
                errors.append(f"request {i}: {type(e).__name__}: {e}")
        answers.append(x)
    if errors:
        print(f"{len(errors)} requests failed; the first: {errors[0]}",
              flush=True)
    t_end = time.perf_counter()
    if run.trace:
        run.stop_trace()
    state["answers"] = answers
    ok = [i for i, x in enumerate(answers)
          if x is not None and np.isfinite(done[i])]
    lat = [done[i] - (t0 + due[i]) for i in ok]
    if lat:
        run.readings["serve_p50_s"] = percentile(lat, 50)
        run.readings["serve_p95_s"] = percentile(lat, 95)
    run.readings["latencies_in_due_order"] = lat
    run.readings["generator_lag_s"] = list(sent - (t0 + due))
    run.readings["serve_snapshot"] = state["svc"].metrics.snapshot()
    run.notes.update(
        generator_lag_max_s=float(np.max(sent - (t0 + due))),
        requests=len(due), completed=len(ok),
        offered_per_s=len(due) / run.seconds,
        completed_per_s=len(ok) / (t_end - t0),
        drain_s=t_end - t0 - run.seconds)


def check(run, state) -> dict:
    checker = Checker(state["mats"], run.config["guarantees"])
    pool = state["pool"]
    answers = [(0, pool[i % len(pool)][1], pool[i % len(pool)][0], x)
               for i, x in enumerate(state["answers"])]
    return checker.judge(answers)


def close(run, state) -> None:
    state["svc"].close()
