#!/bin/bash
# Runs of one cell that keep each run's log (the server's stderr too: a slow
# dispatch writes every thread's stack there) and the batcher's dispatch
# rings; one summary line a run goes to chiprun_out/<tag>.jsonl.
#   bash benchmark/findings/run_kept.sh <tag> <dir of the checkout> <cell> <seconds> <trace 0|1> <seed>...
tag=$1; dir=$2; cell=$3; seconds=$4; trace=$5; shift 5
root=$(pwd); mkdir -p $root/chiprun_out/$tag
for seed in "$@"; do
  log=$root/chiprun_out/$tag/seed$seed.trace$trace.log
  rings=$root/chiprun_out/$tag/seed$seed.trace$trace.dispatches.json
  ( cd $dir && python3 benchmark/findings/keep_dispatches.py $rings \
      --workload $cell --seed $seed --seconds $seconds --trace $trace ) > $log 2>&1
  rc=$?
  slow=$(grep -c "held the batcher" $log)
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"slow_dispatch_warnings\": $slow, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "set-up done|requests:|latency ms|by rung" $log | sed "s/^/$tag seed$seed /"
done
