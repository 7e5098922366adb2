#!/bin/bash
# Two sets of six runs of one cell (the same seeds in both), then one traced
# run; every result line goes to chiprun_out/sets.<cell>.jsonl.
#   chiprun --timeout 3000 -- bash benchmark/findings/run_sets.sh <cell> <first-seed> <seconds>
cell=$1; first=$2; seconds=$3
mkdir -p chiprun_out/sets
out=chiprun_out/sets.$cell.jsonl; : > $out
for set in 1 2; do
  for k in 0 1 2 3 4 5; do
    seed=$((first + k))
    log=chiprun_out/sets/$cell.set$set.seed$seed.log
    PIO_BENCH_KEEP_RECORDS=chiprun_out/sets/$cell.set$set.seed$seed.records.json \
      python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 > $log 2>&1
    rc=$?
    echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -1 $log)}" >> $out
    grep -E "set-up done|audit took|requests:|latency ms|by rung" $log | sed "s/^/set$set seed$seed /"
  done
done
log=chiprun_out/sets/$cell.trace.log
python3 benchmark/run.py --workload $cell --seed $((first + 6)) --seconds $seconds --trace 1 > $log 2>&1
echo "{\"set\": \"trace\", \"seed\": $((first + 6)), \"rc\": $?, \"result\": $(tail -1 $log)}" >> $out
cat $out
