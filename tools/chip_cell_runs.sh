#!/bin/bash
# Run one benchmark cell several times in ONE chiprun call and say how long each
# run took from start to result: the driver stops a run at its time limit (a
# first run in a checkout 1200 s, a later one 360 s; ISSUE 29), and `setup_s`
# alone does not show the drain and the reference after the window.
#
#   chiprun --timeout 2300 -- bash tools/chip_cell_runs.sh <cell> <out> <seed>:<trace> ...
#
# The first run compiles (nothing is cached in a new machine), the others read
# the cache. Output and server logs go to chiprun_out/<out>/.
set -u
cell=$1; out=chiprun_out/$2; shift 2
mkdir -p "$out"
t_call=$(date +%s)
i=0
for run in "$@"; do
  seed=${run%%:*}; trace=${run##*:}
  t0=$(date +%s)
  python benchmark/run.py --workload "$cell" --seed "$seed" --seconds 45 --trace "$trace" \
    > "$out/run_$i.out" 2> "$out/run_$i.err"
  rc=$?
  t1=$(date +%s)
  echo "run $i seed=$seed trace=$trace rc=$rc wall_s=$((t1 - t0)) (at $((t1 - t_call))s of the call)"
  grep -h '"phase": "\(checkpoint\|ready\|first_background_compile\|warm\|compiles_at_rest\|correctness\)"' "$out/run_$i.out" | cut -c1-400
  tail -n 1 "$out/run_$i.out" | cut -c1-1800
  grep -h '^compared:\|^correct:' "$out/run_$i.err"
  cp "benchmark/.cache/work/$cell/server.log" "$out/server_$i.log" 2>/dev/null
  i=$((i + 1))
done
