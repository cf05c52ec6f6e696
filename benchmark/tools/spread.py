#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over two sets of runs, as the
benchmark's contract measures them: the distance between the first and
the third quartile (`statistics.quantiles(values, n=4)`) as a share of
the median, for each set, and the wider of the two.

    python3 benchmark/tools/spread.py set1/*.out -- set2/*.out

Each file's last line that parses as JSON with a `metrics` key is a
run.  The first run of a cell in a checkout compiles: its `setup_s` is
left out, as the driver leaves it out.
"""

import json
import statistics
import sys


def last_result(path):
    with open(path) as f:
        for line in reversed(f.read().strip().splitlines()):
            if line.startswith("{"):
                rec = json.loads(line)
                if "metrics" in rec:
                    return rec
    return None


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> int:
    cut = argv.index("--") if "--" in argv else len(argv)
    sets = [argv[:cut], argv[cut + 1:]]
    report = {}
    for si, files in enumerate(sets):
        runs = [r for r in map(last_result, files) if r]
        for r in runs:
            if not r["correct"]:
                print(f"NOT CORRECT: {r['workload']} seed {r['seed']}")
        for name in sorted({m for r in runs for m in r["metrics"]}):
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            if name == "setup_s":
                # a compiling first run is told by its set-up
                vals = [v for v in vals if v < 3 * min(vals)]
            if len(vals) >= 2:
                report.setdefault(name, []).append(
                    {"set": si + 1, "n": len(vals),
                     "median": statistics.median(vals),
                     "spread": spread(vals),
                     "min": min(vals), "max": max(vals)})
    for name, rows in report.items():
        for row in rows:
            print(json.dumps({"metric": name, **row}))
        wide = max(r["spread"] for r in rows)
        line = {"metric": name, "wider_spread": wide,
                "five_times": 5 * wide}
        if len(rows) == 2:
            line["second_median_over_first"] = (rows[1]["median"]
                                                / rows[0]["median"])
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
