#!/bin/bash
# Parent against change in one call, on the same chip: for each seed the two
# trees run one after the other, the order alternating (parent, change,
# change, parent, ...).  Every result line goes to chiprun_out/<tag>.jsonl
# with its side and seed; every log is kept under chiprun_out/<tag>/.  Runs
# through serve_rings.py, so each run also keeps the whole of `GET /`
# (batcher counters, dispatches by rung) and the dispatch ring.
#   chiprun --timeout 3000 -- bash tools/chip_probes/pairs.sh <tag> <parent dir> <cell> <seconds> <trace 0|1> <seed>...
# The change is this checkout, or the directory CHANGE names (an unpacked
# `git archive $(git write-tree)`: the committed files alone).  KEEP_TRACES=1
# keeps each traced run's slice under chiprun_out/<tag>/<side>.seed<n>.trace
# (for `turnaround_split.py --device-gap`, `hostjoin.py`).
tag=$1; parent=$2; cell=$3; seconds=$4; trace=$5; shift 5
root=$(pwd); mkdir -p $root/chiprun_out/$tag
k=0
for seed in "$@"; do
  if [ $((k % 2)) = 0 ]; then order="parent change"; else order="change parent"; fi
  k=$((k + 1))
  for side in $order; do
    if [ $side = parent ]; then dir=$parent; else dir=${CHANGE:-$root}; fi
    log=$root/chiprun_out/$tag/$side.seed$seed.log
    rings=$root/chiprun_out/$tag/$side.seed$seed.rings.json
    ( cd $dir && env ${KEEP_TRACES:+PIO_BENCH_KEEP_TRACE=$root/chiprun_out/$tag/$side.seed$seed.trace} \
        python3 $root/tools/chip_probes/serve_rings.py $rings \
        --workload $cell --seed $seed --seconds $seconds --trace $trace ) > $log 2>&1
    rc=$?
    echo "{\"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
    grep -E "set-up done|requests:|latency ms|by rung" $log | sed "s/^/$side seed$seed /"
  done
done
