#!/bin/bash
# PR 41's cell on the chip, call by call (every run another seed):
#   look   the family's first contact (tools/chip_probes/ssd_first_look.py:
#          per-rung device time by op), a traced run of the cell at a guessed
#          rate, and the limits' readings over two seeds (check_ssd.py)
#   knee   the knee from two sweeps (run_knee.sh); the lower goes into knee_rps
#          of this checkout's configuration, then runs of the cell
#   check  the limits' controls over $SEEDS seeds (check_ssd.py)
#   cell   $RUNS runs of the cell (from $DIR, default this checkout) and a traced one
#   refuse the parent asked for the new cell (as it stands: unknown workload;
#          with this PR's BENCHMARK.json and benchmark/ laid over it: the engine
#          cannot import the family) must fail at once
#   others the three older sequence cells, parent and change, one untraced pair each.
#          Before the call:  rm -rf .bench_archive; mkdir -p .bench_archive/parent
#          .bench_archive/overlaid .bench_archive/change; git archive <parent> | tar -x -C
#          .bench_archive/parent; cp -r .bench_archive/parent/. .bench_archive/overlaid/;
#          cp BENCHMARK.json .bench_archive/overlaid/; cp -r benchmark/. .bench_archive/overlaid/benchmark/;
#          git archive $(git write-tree) | tar -x -C .bench_archive/change
#   chiprun --timeout 2400 -- bash benchmark/findings/run_pr41.sh look|knee|check|cell|refuse|others
root=$(pwd); cell=falcon-h1-l6.serve-steady; config=falcon-h1-34b-l6
run() {  # tag trace dir seed [extra args]
  tag=$1; trace=$2; dir=$3; seed=$4; shift 4; mkdir -p $root/chiprun_out/$tag
  log=$root/chiprun_out/$tag/seed$seed.log
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace "$@" ) > $log 2>&1
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"trace\": $trace, \"rc\": $?, \"result\": $(tail -1 $log | grep '^{' || echo null)}" >> $root/chiprun_out/$tag.jsonl
  grep -E "set-up done|deployed|requests:|latency ms|by rung|audit took|check trunk|served score|Error|error" $log | sed "s/^/$tag seed$seed /" | cut -c1-420
}
case "$1" in
look)
  python3 tools/chip_probes/ssd_first_look.py 2>&1 | grep -vE "^WARNING|^I0|^W0" | tail -40 | cut -c1-900
  run falcon.look 1 . 4100000101 --rate ${RATE:-4}
  tail -1 chiprun_out/falcon.look.jsonl | cut -c1-6000
  python3 benchmark/check_ssd.py --config $config --seeds ${SEEDS:-2} --first-seed 4100000201 2>&1 | grep -E "^\[check_ssd\]|^\{|Error|error" | cut -c1-4000
  ;;
knee)
  bash benchmark/findings/run_knee.sh $config serve-steady ${SWEEP_S:-30} ${RATES:-6,9,12,15,18}
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
  for i in $(seq 1 ${RUNS:-3}); do run falcon.set1 0 . 410000030$i; done
  run falcon.traced 1 . 4100000307
  tail -1 chiprun_out/falcon.traced.jsonl | cut -c1-6000
  ;;
check)
  python3 benchmark/check_ssd.py --config $config --seeds ${SEEDS:-8} --first-seed ${SEED0:-4100000401} 2>&1 \
    | grep -E "^\[check_ssd\]|^\{|Error|error" | cut -c1-3000
  ;;
cell)
  for i in $(seq 1 ${RUNS:-6}); do run falcon.${TAG:-set2} 0 ${DIR:-.} ${SEED0:-41000005}0$i; done
  for i in $(seq 1 ${TRACED:-0}); do run falcon.${TAG:-set2}.traced 1 ${DIR:-.} ${SEED0:-41000005}9$i; tail -1 chiprun_out/falcon.${TAG:-set2}.traced.jsonl | cut -c1-6000; done
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
    echo "{\"tag\": \"$1\", \"cell\": \"$3\", \"seed\": $5, \"trace\": $4, \"rc\": $?, \"result\": $(tail -1 chiprun_out/others/$1.log | grep '^{' || echo null)}" >> chiprun_out/others.pr41.jsonl
    tail -1 chiprun_out/others.pr41.jsonl | cut -c1-1200
  }
  old parent.olmo .bench_archive/parent olmo-hybrid-l16.serve-steady 0 4100000603
  old change.olmo .bench_archive/change olmo-hybrid-l16.serve-steady 0 4100000603
  old change.trinity .bench_archive/change trinity-large-l5.serve-steady 0 4100000604
  old parent.trinity .bench_archive/parent trinity-large-l5.serve-steady 0 4100000604
  old parent.joyai .bench_archive/parent joyai-flash-l5.serve-steady 0 4100000602
  old change.joyai .bench_archive/change joyai-flash-l5.serve-steady 0 4100000602
  ;;
esac
