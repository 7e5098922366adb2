#!/bin/bash
# Several checkouts of the program on one cell in one call, untraced, round
# after round on a fresh seed with the order rotated: which PART of a change
# moved an end-to-end metric (PR 37: the change read 0.4 ms under its parent
# on the ALS cell though it only adds instrumentation).  Every result line
# goes to chiprun_out/<tag>.jsonl with the checkout's name.
#   chiprun --timeout 3000 -- bash tools/chip_probes/variants.sh <tag> <cell> <seconds> <first seed> <rounds> <dir>...
tag=$1; cell=$2; seconds=$3; seed=$4; rounds=$5; shift 5
root=$(pwd); mkdir -p $root/chiprun_out/$tag
dirs=("$@"); n=${#dirs[@]}
for ((r = 0; r < rounds; r++)); do
  for ((k = 0; k < n; k++)); do
    dir=${dirs[$(((k + r) % n))]}; name=$(basename $dir)
    log=$root/chiprun_out/$tag/$name.seed$seed.log
    ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 ) > $log 2>&1
    echo "{\"side\": \"$name\", \"seed\": $seed, \"rc\": $?, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
    echo "$name seed$seed $(grep -E 'latency ms' $log | cut -c18-)"
  done
  seed=$((seed + 1))
done
