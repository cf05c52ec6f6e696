#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the TPU it is started on and prints,
as the last line of its standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device`.  It exits
with a code other than 0, and prints no result, where jax finds no TPU
or fewer chips than the cell asks for, or where the program is not
there.  See harness.py for how a cell's files are found.
"""

import sys
import time

T_START = time.perf_counter()   # set-up counts from process start

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
