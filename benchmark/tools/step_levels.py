#!/usr/bin/env python3
"""Where a step cell's `step_s` spreads between runs.  One process,
set-up paid once; then, for each of several seeds, two turns of the
ring with every step's factorize and solve walls and its refinement
passes, so that a seed's value sets (a pass more on some) and the
process's own level (the host's seconds, the same on every seed) can
be told apart; then the same steps with the main thread pinned to
each core in turn.  Run it in two processes on the same seeds: what
differs between them on one seed is the process's.  One JSON line a
seed and a core.

    python3 benchmark/tools/step_levels.py --workload <cell> \
        --seeds 6 [--turns 2] [--rehearse-cpu]
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402


def cpu_of_main() -> int:
    """The core the main thread last ran on (/proc/self/stat)."""
    with open("/proc/self/stat") as f:
        return int(f.read().rsplit(")", 1)[1].split()[36])


def steps(run, kind, state, count: int):
    """`count` steps as the window makes them, timed in two parts."""
    rows = []
    for i in range(count):
        j = i % len(state["mats"])
        st = run.slu.Stats()
        t0 = time.perf_counter()
        lu = run.slu.factorize(state["csr"][j], state["opts"],
                               plan=state["plan"], grid=state["grid"])
        kind._block(run.jax, lu)
        t1 = time.perf_counter()
        np.asarray(run.slu.solve(lu, state["systems"][j][1], stats=st))
        rows.append((t1 - t0, time.perf_counter() - t1,
                     int(st.refine_steps)))
    return rows


def summary(rows) -> dict:
    walls = [f + s for f, s, _ in rows]
    by_passes: dict[int, list] = {}
    for _, s, p in rows:
        by_passes.setdefault(p, []).append(s)
    return {"step_mean_s": statistics.fmean(walls),
            "step_median_s": statistics.median(walls),
            "factorize_median_s": statistics.median(r[0] for r in rows),
            "solve_median_s": statistics.median(r[1] for r in rows),
            "solve_median_s_by_passes": {
                p: statistics.median(v) for p, v in by_passes.items()},
            "cpu": cpu_of_main()}


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=6)
    p.add_argument("--first-seed", type=int, default=2_147_500_000)
    p.add_argument("--turns", type=int, default=2)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    try:
        run, kind = harness.start(args.workload, args.first_seed, 30.0,
                                  t_start=T_START,
                                  rehearse=args.rehearse_cpu)
    except harness.Refused as e:
        print(f"step_levels: {e}. No result.", file=sys.stderr)
        return 2
    if run.traffic["kind"] not in ("step", "zstep"):
        print("step_levels: a step cell, please.", file=sys.stderr)
        return 2
    state = kind.setup(run)
    harness.settle(run)
    cores = sorted(os.sched_getaffinity(0))
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": run.device, "cores": cores,
                      "load": os.getloadavg(),
                      "threads": len(os.listdir("/proc/self/task")),
                      "cpu": cpu_of_main()}), flush=True)
    ring = len(state["mats"])
    seed = args.first_seed
    for _ in range(args.seeds):
        seed += 7919
        kind.reseed(run, state, seed)
        rows = steps(run, kind, state, args.turns * ring)
        print(json.dumps({"seed": seed,
                          "passes": [r[2] for r in rows[:ring]],
                          **summary(rows)}), flush=True)
    # the main thread alone moves (pid 0 is the calling thread); the
    # runtime's threads stay where they are
    for c in cores:
        os.sched_setaffinity(0, {c})
        print(json.dumps({"pinned": c,
                          **summary(steps(run, kind, state, ring))}),
              flush=True)
    os.sched_setaffinity(0, set(cores))
    print(json.dumps({"pinned": None, "load": os.getloadavg(),
                      **summary(steps(run, kind, state, 2 * ring))}),
          flush=True)
    kind.close(run, state)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
