#!/bin/bash
# Per rung, the bare sequence program on one history that fills the rung
# (n = R) and on half the rung and one event (n = R/2 + 1), parent against
# change on the same chip in one call (PR 42: the dense sublayers run only
# the token tiles that hold a real token).
#   chiprun --timeout 3000 -- bash tools/chip_probes/pr42_rungs.sh <tag> <parent dir> <wmoe|ssd|gdn>...
# The parent dir: `git archive` of the parent with this tree's three
# *_first_look.py copied over its own (the parent's lack the second length).
# Results: chiprun_out/<tag>/<family>_first_look.<side>.json, logs beside.
tag=$1; parent=$(cd $2 && pwd); shift 2
root=$(pwd); mkdir -p $root/chiprun_out/$tag
for fam in "$@"; do
  for side in parent change; do
    if [ $side = parent ]; then dir=$parent; else dir=$root; fi
    out=$root/chiprun_out/$tag/${fam}_first_look.$side.json
    if [ $fam = gdn ]; then args="--rungs"; else args="--lengths one_row,half_plus_one"; fi
    ( cd $dir && python3 tools/chip_probes/${fam}_first_look.py $args --out $out ) \
      > $root/chiprun_out/$tag/${fam}_first_look.$side.log 2>&1
    echo "$fam $side rc=$?"
    grep -E "^[0-9]+ (one_row|half_plus_one|full) " $root/chiprun_out/$tag/${fam}_first_look.$side.log \
      | sed -E "s/'other_top'.*//; s/^/$fam $side /" | cut -c1-200
  done
done
