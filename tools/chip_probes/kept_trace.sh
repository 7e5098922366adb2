#!/bin/bash
# One traced run of each named cell that keeps the profiler's slice
# (PIO_BENCH_KEEP_TRACE), the client's records and the log, for
# `python3 benchmark/pio_bench/hostjoin.py <kept trace>` afterwards.
#   chiprun --timeout 1800 -- bash tools/chip_probes/kept_trace.sh <tag> <seed> <cell>...
# Run from the checkout to be measured (DIR names another, e.g. the parent's
# unpacked archive); everything lands under chiprun_out/<tag>/.
tag=$1; seed=$2; shift 2
root=$(pwd); mkdir -p $root/chiprun_out/$tag
for cell in "$@"; do
  log=$root/chiprun_out/$tag/$cell.log
  ( cd ${DIR:-$root} && \
    PIO_BENCH_KEEP_TRACE=$root/chiprun_out/$tag/$cell.trace \
    PIO_BENCH_KEEP_RECORDS=$root/chiprun_out/$tag/$cell.records.json \
    python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace 1 ) > $log 2>&1
  echo "{\"tag\": \"$tag\", \"cell\": \"$cell\", \"seed\": $seed, \"rc\": $?, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "set-up done|requests:|latency ms|by rung|request traces" $log | sed "s/^/$cell /"
  seed=$((seed + 1))
done
du -sh $root/chiprun_out/$tag
