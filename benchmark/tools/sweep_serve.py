#!/usr/bin/env python3
"""The rate sweep that finds a served cell's knee, once, on the chip:
one process, set-up paid once, one window per offered rate.  A rate is
sustained when the window's requests complete at the offered rate, the
queue is empty soon after the last one (`drain_s`), and the second
half of the window is no slower than the first.

    python3 benchmark/tools/sweep_serve.py --workload <cell> \
        --rates 10,15,20,25,30,40 --seconds 20
"""

import argparse
import json
import os
import sys
import time

import numpy as np

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=2_147_600_000)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args(argv)
    try:
        run, kind = harness.start(args.workload, args.seed,
                                  args.seconds, t_start=T_START,
                                  rehearse=args.rehearse_cpu)
    except harness.Refused as e:
        print(f"sweep: {e}. No result.", file=sys.stderr)
        return 2
    state = kind.setup(run)
    harness.settle(run)
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": run.device}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.traffic = dict(run.traffic, rate_per_s=rate)
        run.seed = args.seed + i
        kind.window(run, state)
        v = kind.check(run, state)
        lat = np.asarray(run.readings["latencies_in_due_order"])
        half = len(lat) // 2
        rec = {"rate_per_s": rate, "failed": v["failed"],
               **run.notes,
               "p50_first_half_s": float(np.median(lat[:half])),
               "p50_second_half_s": float(np.median(lat[half:]))}
        if not args.rehearse_cpu:
            rec.update(serve_p50_s=run.readings["serve_p50_s"],
                       serve_p95_s=run.readings["serve_p95_s"])
        print(json.dumps(rec), flush=True)
    kind.close(run, state)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
