#!/bin/sh
# A cell's proof in one chip call, sharing one compile cache: its timed
# runs in sets with the same seeds, one traced run, and many seeds with
# the controls in one process.  Results land under chiprun_out/<tag>/.
#
#   chiprun --chips <n> --timeout 3400 -- \
#       sh benchmark/tools/prove.sh <workload> <tag>
#
# Environment: SETS (2), SEEDS (six large ones), SOUND (12) and
# CONTROL (3) seeds of seeds.py with windows of WINDOW (6) seconds.
W=$1; O=chiprun_out/$2; mkdir -p "$O"
SETS=${SETS:-2}
SEEDS=${SEEDS:-"2147483701 2147484313 2147485127 2147486003 2147487111 2147488279"}
SECONDS_=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
R="python3 benchmark/run.py --workload $W --seconds $SECONDS_"
set=1
while [ "$set" -le "$SETS" ]; do
  for s in $SEEDS; do
    $R --seed "$s" --trace 0 > "$O/set${set}_$s.out" 2> "$O/set${set}_$s.err"
    echo "rc=$?" >> "$O/set${set}_$s.err"
  done
  set=$((set + 1))
done
$R --seed 2147490021 --trace 1 > "$O/trace.out" 2> "$O/trace.err"
echo "rc=$?" >> "$O/trace.err"
python3 benchmark/tools/trace_look.py ".bench_out/trace/$W" && \
  cp ".bench_out/trace/$W/layout.json" "$O/layout.json"
python3 benchmark/tools/seeds.py --workload "$W" --seeds "${SOUND:-12}" \
  --control-seeds "${CONTROL:-3}" --seconds "${WINDOW:-6}" \
  > "$O/seeds.out" 2> "$O/seeds.err"
echo "rc=$?" >> "$O/seeds.err"
tail -n 1 "$O/trace.out" | cut -c1-3000
cut -c1-330 "$O/seeds.out"
if [ "$SETS" -ge 2 ]; then
  python3 benchmark/tools/spread.py "$O"/set1_*.out -- "$O"/set2_*.out
else
  python3 benchmark/tools/spread.py "$O"/set1_*.out
fi
