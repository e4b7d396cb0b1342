#!/bin/bash
# run_many.sh <tag> <workload> <seconds> <trace> <seed>...  — several runs of one
# cell in one chip call; each result line goes to chiprun_out/<tag>.jsonl
tag=$1; wl=$2; secs=$3; trace=$4; shift 4
mkdir -p chiprun_out
for seed in "$@"; do
  t0=$(date +%s.%N)
  python3 benchmark/run.py --workload "$wl" --seed "$seed" --seconds "$secs" --trace "$trace" \
    > chiprun_out/.out 2> chiprun_out/.err
  rc=$?
  t1=$(date +%s.%N)
  echo "run seed=$seed trace=$trace rc=$rc total_s=$(python3 -c "print($t1 - $t0)")"
  grep -aE "^judged|^check|FAILED|^note" chiprun_out/.err | tail -n 16
  tail -n 1 chiprun_out/.out | head -c 1200; echo
  tail -n 1 chiprun_out/.out >> chiprun_out/$tag.jsonl
  if [ $rc -ne 0 ]; then tail -n 30 chiprun_out/.err; fi
done
