#!/bin/bash
# PR 34's cell on the chip, in two calls (every run another seed; each run
# through tools/chip_probes/serve_rings.py, which keeps `GET /` and the
# dispatch ring beside the log):
#   knee   the knee from two sweeps (run_knee.sh: a coarse one, a fine one
#          around it); the lower of the two goes into knee_rps, and
#          docs/operations.md's gate for 0.3 x it into serving.max_inflight, of
#          this checkout's configuration (written into the file by hand
#          afterwards); then six runs of the cell and a traced run
#   final  the limits' controls over eight seeds (check_gdn.py), then runs of
#          the cell from .bench_archive/change (an unpacked `git archive
#          $(git write-tree)`: the committed files alone) for as long as
#          $BUDGET_S seconds allow, six at most
#   chiprun --timeout 1500 -- bash benchmark/findings/run_pr34.sh knee|final
root=$(pwd); cell=olmo-hybrid-l16.serve-steady; config=olmo-hybrid-7b-l16
t_start=$(date +%s)
run() {  # tag trace dir seed
  tag=$1; trace=$2; dir=$3; seed=$4; mkdir -p $root/chiprun_out/$tag
  log=$root/chiprun_out/$tag/seed$seed.log
  ( cd $dir && python3 $root/tools/chip_probes/serve_rings.py $root/chiprun_out/$tag/seed$seed.rings.json \
      --workload $cell --seed $seed --seconds 40 --trace $trace ) > $log 2>&1
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"trace\": $trace, \"rc\": $?, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "set-up done|requests:|latency ms|by rung|audit took|check trunk|served score" $log | sed "s/^/$tag seed$seed /" | cut -c1-330
}
if [ "$1" = knee ]; then
  bash benchmark/findings/run_knee.sh $config serve-steady 15 45,55,65,75
  python3 - $config <<'PY'
import json, math, sys
config = sys.argv[1]
knees = [json.load(open(f"chiprun_out/sweep.{config}.serve-steady.{t}.json"))["knee_rps"]
         for t in ("coarse", "fine")]
knee = min(k for k in knees if k)
rate = 0.3 * knee
gate = 32 * math.ceil((0.3 + 15.0) * rate / 32)  # p99 x qps + 15 s of stall x qps
path = f"benchmark/configs/{config}.json"
cfg = json.load(open(path))
cfg["knee_rps"], cfg["serving"]["max_inflight"] = knee, gate
open(path, "w").write(json.dumps(cfg, indent=2, ensure_ascii=False))
print(f"knees {knees}: knee_rps {knee}, the cell offers {rate} req/s, max_inflight {gate}")
PY
  for i in 1 2 3 4 5 6; do run olmo.set1 0 . ${SEED0:-345}000000$i; done
  run olmo.traced 1 . ${SEED0:-345}0000007
  tail -1 chiprun_out/olmo.traced.jsonl | cut -c1-3000
else
  python3 benchmark/check_gdn.py --config $config --seeds 8 --first-seed ${SEED0:-345}0000021 2>&1 \
    | grep -E "^\[check_gdn\]|^\{" | cut -c1-1500
  for i in 1 2 3 4 5 6; do
    [ $(( $(date +%s) - t_start + 155 )) -gt ${BUDGET_S:-900} ] && break
    run olmo.set2 0 .bench_archive/change ${SEED0:-345}000001$i
  done
fi
