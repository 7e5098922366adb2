#!/bin/bash
# PR 46's cell on the chip, call by call (every run another seed):
#   look   the family's first contact (tools/chip_probes/smoe_first_look.py:
#          per-rung device time by op), a traced run of the cell at a guessed
#          rate, and the limits' readings over two seeds (check_smoe.py)
#   knee   the knee from two sweeps (run_knee.sh); the lower goes into knee_rps
#          of this checkout's configuration, then runs of the cell
#   check  the limits' controls over $SEEDS seeds (check_smoe.py)
#   cell   $RUNS runs of the cell (from $DIR, default this checkout) and a traced one
#   refuse the parent asked for the new cell (as it stands: unknown workload;
#          with this PR's BENCHMARK.json and benchmark/ laid over it: the engine
#          cannot import the family) must fail at once
#   others the four older sequence cells, parent and change, one untraced pair each.
#          Before the call:  rm -rf .bench_archive; mkdir -p .bench_archive/parent
#          .bench_archive/overlaid .bench_archive/change; git archive <parent> | tar -x -C
#          .bench_archive/parent; cp -r .bench_archive/parent/. .bench_archive/overlaid/;
#          cp BENCHMARK.json .bench_archive/overlaid/; cp -r benchmark/. .bench_archive/overlaid/benchmark/;
#          git archive $(git write-tree) | tar -x -C .bench_archive/change
#   traced one old cell with --trace 1 from .bench_archive/overlaid (the parent under this PR's benchmark files)
#   rerun  after the review: the first contact's two check seeds (4600000201-202, whose lines
#          the second call's copy of the file replaced) and the cell on seed 4600000301 (the
#          run that read served beat_over_tol 1.22 of 0.004 and so `correct` false), under the
#          committed limits, from .bench_archive/change; two fresh seeds beside it
#   chiprun --timeout 3000 -- bash benchmark/findings/run_pr46.sh <phase> [<phase> ...]   (look knee check cell rerun refuse others traced)
root=$(pwd); cell=granite-h-small-l10.serve-steady; config=granite-4.0-h-small-l10-ep2
run() {  # tag trace dir seed [extra args]
  tag=$1; trace=$2; dir=$3; seed=$4; shift 4; mkdir -p $root/chiprun_out/$tag
  log=$root/chiprun_out/$tag/seed$seed.log
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace "$@" ) > $log 2>&1
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"trace\": $trace, \"rc\": $?, \"result\": $(tail -1 $log | grep '^{' || echo null)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "set-up done|deployed|requests:|latency ms|by rung|audit took|check trunk|served score|Error|error" $log | sed "s/^/$tag seed$seed /" | cut -c1-420
}
for phase in "$@"; do
case "$phase" in
look)
  python3 tools/chip_probes/smoe_first_look.py ${LOOK_ARGS:-} 2>&1 | grep -vE "^WARNING|^I0|^W0" | tail -60 | cut -c1-1400
  run granite.look 1 . 4600000101 --rate ${RATE:-4}
  tail -1 chiprun_out/granite.look.jsonl | cut -c1-6000
  python3 benchmark/check_smoe.py --config $config --seeds ${SEEDS:-2} --first-seed 4600000201 2>&1 | grep -E "^\[check_smoe\]|^\{|Error|error" | cut -c1-4000
  ;;
knee)
  bash benchmark/findings/run_knee.sh $config serve-steady ${SWEEP_S:-30} ${RATES:-8,12,16,20,24}
  python3 - $config <<'PY'
import json, math, sys
config = sys.argv[1]
knees = [json.load(open(f"chiprun_out/sweep.{config}.serve-steady.{t}.json"))["knee_rps"]
         for t in ("coarse", "fine")]
knee = min(k for k in knees if k)
rate = 0.3 * knee
gate = max(256, 32 * math.ceil((1.0 + 15.0) * rate / 32))  # p99 x qps + 15 s of stall x qps
path = f"benchmark/configs/{config}.json"
cfg = json.load(open(path))
cfg["knee_rps"], cfg["serving"]["max_inflight"] = knee, gate
open(path, "w").write(json.dumps(cfg, indent=1, ensure_ascii=False))
print(f"knees {knees}: knee_rps {knee}, the cell offers {rate} req/s, max_inflight {gate}")
PY
  for i in $(seq 1 ${RUNS:-3}); do run granite.set1 0 . 460000030$i; done
  run granite.traced 1 . 4600000307
  tail -1 chiprun_out/granite.traced.jsonl | cut -c1-6000
  ;;
check)
  python3 benchmark/check_smoe.py --config $config --seeds ${SEEDS:-8} --first-seed ${SEED0:-4600000401} 2>&1 \
    | grep -E "^\[check_smoe\]|^\{|Error|error" | cut -c1-3000
  ;;
cell)
  for i in $(seq 1 ${RUNS:-6}); do run granite.${TAG:-set2} 0 ${DIR:-.} ${SEED0:-46000005}0$i; done
  for i in $(seq 1 ${TRACED:-0}); do run granite.${TAG:-set2}.traced 1 ${DIR:-.} ${SEED0:-46000005}9$i; tail -1 chiprun_out/granite.${TAG:-set2}.traced.jsonl | cut -c1-6000; done
  ;;
rerun)
  python3 benchmark/check_smoe.py --config $config --seeds 2 --first-seed 4600000201 2>&1 \
    | grep -E "^\[check_smoe\]|^\{|Error|error" | cut -c1-3000
  for s in 4600000301 4600000311 4600000312; do run granite.rerun 0 .bench_archive/change $s; done
  ;;
refuse)
  for d in parent overlaid; do
    t0=$(date +%s.%N)
    ( cd .bench_archive/$d && timeout 300 python3 benchmark/run.py --workload $cell --seed 1 --seconds 40 ) > chiprun_out/newcell_on_$d.log 2>&1
    echo "new cell on $d: exit $? after $(python3 -c "import time; print(round(time.time() - $t0, 1))") s: $(tail -1 chiprun_out/newcell_on_$d.log | cut -c1-200)"
  done
  ;;
others)
  old() {  # tag dir cell trace seed
    mkdir -p chiprun_out/others
    ( cd $2 && python3 benchmark/run.py --workload $3 --seed $5 --seconds 40 --trace $4 ) > chiprun_out/others/$1.log 2>&1
    echo "{\"tag\": \"$1\", \"cell\": \"$3\", \"seed\": $5, \"trace\": $4, \"rc\": $?, \"result\": $(tail -1 chiprun_out/others/$1.log | grep '^{' || echo null)}" >> chiprun_out/others.pr46.jsonl
    tail -1 chiprun_out/others.pr46.jsonl | cut -c1-1200
  }
  old parent.olmo .bench_archive/parent olmo-hybrid-l16.serve-steady 0 4600000603
  old change.olmo .bench_archive/change olmo-hybrid-l16.serve-steady 0 4600000603
  old change.trinity .bench_archive/change trinity-large-l5.serve-steady 0 4600000604
  old parent.trinity .bench_archive/parent trinity-large-l5.serve-steady 0 4600000604
  old parent.joyai .bench_archive/parent joyai-flash-l5.serve-steady 0 4600000602
  old change.joyai .bench_archive/change joyai-flash-l5.serve-steady 0 4600000602
  old change.falcon .bench_archive/change falcon-h1-l6.serve-steady 0 4600000605
  old parent.falcon .bench_archive/parent falcon-h1-l6.serve-steady 0 4600000605
  ;;
traced)
  mkdir -p chiprun_out/others
  for d in overlaid change; do
    ( cd .bench_archive/$d && python3 benchmark/run.py --workload ${OLD:-falcon-h1-l6.serve-steady} --seed 4600000701 --seconds 40 --trace 1 ) > chiprun_out/others/traced.$d.log 2>&1
    echo "{\"tag\": \"traced.$d\", \"cell\": \"${OLD:-falcon-h1-l6.serve-steady}\", \"rc\": $?, \"result\": $(tail -1 chiprun_out/others/traced.$d.log | grep '^{' || echo null)}" >> chiprun_out/others.pr46.jsonl
    tail -1 chiprun_out/others.pr46.jsonl | cut -c1-3000
  done
  ;;
esac
done
