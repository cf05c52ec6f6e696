#!/usr/bin/env python3
"""Many seeds of one cell in one process: the sound program first,
then each of the configuration's controls (a lower precision in the
program's place), with short windows at the cell's own load.  Set-up
is paid once.  Prints one JSON line per window with the numbers
compared beside their limits; this is where the limits' readings in
PERF.md come from.

    python3 benchmark/tools/seeds.py --workload <cell> --seeds 12 \
        --control-seeds 3 --seconds 6 [--rehearse-cpu]
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_147_500_000)
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    try:
        run, kind = harness.start(args.workload, args.first_seed,
                                  args.seconds, t_start=T_START,
                                  rehearse=args.rehearse_cpu)
    except harness.Refused as e:
        print(f"seeds: {e}. No result.", file=sys.stderr)
        return 2
    state = kind.setup(run)
    harness.settle(run)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": run.device}), flush=True)
    plans = [(None, args.seeds)] + [
        (c, args.control_seeds) for c in run.config["controls"]
        if args.control_seeds]
    seed = args.first_seed
    bad = 0
    for control, count in plans:
        if control is not None:
            run.control = control
            kind.warm(run, state)
        for _ in range(count):
            seed += 7919
            run.seed = seed
            kind.reseed(run, state, seed)
            with harness.GcWatch() as watch:
                kind.window(run, state)
            v = kind.check(run, state)
            correct = v["failed"] == 0 and v["attempted"] > 0
            # the sound program has to pass, every control to fail
            bad += correct != (control is None)
            rec = {"control": control, "seed": seed, "correct": correct,
                   "attempted": v["attempted"], "failed": v["failed"],
                   "compared": {c["name"]: c["value"]
                                for c in v["compared"]}}
            if not args.rehearse_cpu:
                rec["readings"] = {k: run.readings.get(k) for k in
                                   ("step_s", "serve_p50_s",
                                    "serve_p95_s")
                                   if k in run.readings}
                rec["gc"] = watch.notes()
                rec["generator_lag_max_s"] = run.notes.get(
                    "generator_lag_max_s")
            print(json.dumps(rec), flush=True)
    kind.close(run, state)
    print(json.dumps({"unexpected": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
