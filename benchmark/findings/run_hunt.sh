#!/bin/bash
# A hunt for a short stall: runs of one cell with the slow-dispatch threshold
# lowered to <hunt seconds> in-process (keep_dispatches.py --hunt), each
# run's log (stack dumps included) and dispatch rings kept.  Never a
# measured run: the poller adds ten requests a second.
#   bash benchmark/findings/run_hunt.sh <tag> <dir of the checkout> <cell> <seconds> <hunt seconds> <seed>...
tag=$1; dir=$2; cell=$3; seconds=$4; hunt=$5; shift 5
root=$(pwd); mkdir -p $root/chiprun_out/$tag
for seed in "$@"; do
  log=$root/chiprun_out/$tag/seed$seed.log
  rings=$root/chiprun_out/$tag/seed$seed.dispatches.json
  ( cd $dir && python3 benchmark/findings/keep_dispatches.py $rings --hunt $hunt \
      --workload $cell --seed $seed --seconds $seconds --trace 0 ) > $log 2>&1
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"rc\": $?, \"dumps\": $(grep -c '^Timeout (' $log), \"slow_dispatch_warnings\": $(grep -c 'held the batcher' $log), \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "requests:|held the batcher" $log | cut -c1-400 | sed "s/^/$tag seed$seed /"
done
