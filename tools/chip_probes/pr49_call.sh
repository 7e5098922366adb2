#!/bin/bash
# PR 49's chip calls (the program store: a warm deploy loads its rungs'
# executables).  Both sides are unpacked archives under .bench_archive/, made
# HERE before the call:
#   bash tools/chip_probes/pr49_call.sh archives
# (`parent`: `git archive` of the parent commit with this tree's
# BENCHMARK.json and benchmark/ laid over it, as the driver lays them;
# `change`: `git archive $(git write-tree)` after `git add -A`), then
#   chiprun --timeout 3500 -- bash tools/chip_probes/pr49_call.sh first|second|third|fourth
# Every cell run goes through `pr48_setup.sh` (-> `setup_in_cell.py`): one
# line a run in chiprun_out/<tag>.setup_in_cell.jsonl with the result line's
# metrics, `programs_loaded`, the store's and the cache's bytes, each rung's
# `compile.<rung>` and the `store.load` / `store.save` phases inside it.
# A side's first run of a cell is a set-up alone (SETUP_IN_CELL_STOP=1), cold:
# it compiles, and on the change's side serializes and writes each rung — its
# `store.save` phases are what the store adds to a cold set-up.
PARENT=20ee0abeec43ad812a5965078611a89f8b247510
G=granite-h-small-l10.serve-steady; O=olmo-hybrid-l16.serve-steady
J=joyai-flash-l5.serve-steady; W=trinity-large-l5.serve-steady
F=falcon-h1-l6.serve-steady; A=wgde-d128.serve-steady
S="bash tools/chip_probes/pr48_setup.sh"
root=$(pwd)

bits() {  # <tag> <config> <side> <name> [VAR=value]: every output of every rung
  tag=$1; config=$2; side=$3; name=$4; shift 4
  mkdir -p $root/chiprun_out/$tag
  ( cd $root/.bench_archive/$side && env "$@" python3 \
      $root/tools/chip_probes/branch_bits.py \
      $root/chiprun_out/$tag/bits.$config.$name.json $config 4900000201 ) \
    > $root/chiprun_out/$tag/bits.$config.$name.log 2>&1
  echo "bits $config $name rc=$? $(tail -1 $root/chiprun_out/$tag/bits.$config.$name.log | cut -c1-300)"
}

compare_bits() {  # <tag> <config>...: parent = compiled = loaded, to the bit
  python3 - "$@" <<'PY'
import json, sys
tag, bad, doc = sys.argv[1], 0, {}
for config in sys.argv[2:]:
    sides = {n: json.load(open(f"chiprun_out/{tag}/bits.{config}.{n}.json"))
             for n in ("parent", "compiled", "loaded")}
    p = sides["parent"]
    differ = [f"{n}.{t}.{k}" for n in ("compiled", "loaded")
              for t in p["rungs"] for k in p["rungs"][t]["outputs"]
              if p["rungs"][t]["outputs"][k]
              != sides[n]["rungs"].get(t, {}).get("outputs", {}).get(k)]
    n_rungs = len(p["rungs"])
    as_meant = (sides["compiled"]["programs_loaded"] == 0
                and sides["loaded"]["programs_loaded"] == n_rungs
                and all(s["compile_count"] == n_rungs for s in sides.values()))
    doc[config] = {
        "device_kind": p["device_kind"], "seed": p["seed"],
        "rungs": list(p["rungs"]),
        "outputs_compared": 2 * sum(len(r["outputs"])
                                    for r in p["rungs"].values()),
        "programs_loaded": {n: s["programs_loaded"] for n, s in sides.items()},
        "compile_count": {n: s["compile_count"] for n, s in sides.items()},
        "differ": differ}
    bad += bool(differ) or not as_meant
    print(config, json.dumps(doc[config]))
json.dump(doc, open(f"chiprun_out/{tag}.bits.json", "w"), indent=1)
sys.exit(1 if bad else 0)
PY
}

verify() {  # <call> <config>...: every entry against a fresh lowering; a
  # chip holds one deployment, so a configuration a process
  call=$1; shift
  for config in "$@"; do
    ( cd $root/.bench_archive/change && python3 tools/verify_program_store.py $config \
        --out $root/chiprun_out/pr49.$call.verify.$config.json ) 2>&1 \
      | grep -v '^E1\|^W1\|^I0\|Transparent\|warnings.warn' | cut -c1-400
    echo "verify $config rc=${PIPESTATUS[0]}"
  done
}

case "${1:-first}" in
archives)
  rm -rf .bench_archive/parent .bench_archive/change
  mkdir -p .bench_archive/parent .bench_archive/change
  git add -A
  tree=$(git write-tree)
  git archive $PARENT | tar -x -C .bench_archive/parent
  git archive $tree BENCHMARK.json benchmark | tar -x -C .bench_archive/parent
  git archive $tree | tar -x -C .bench_archive/change
  echo "parent $PARENT + this tree's benchmark; change $tree"
  ;;
first)
  # ISSUE 49's first question, before anything else is trusted: one JoyAI
  # rung's executable through serialize -> file -> another process
  python3 tools/chip_probes/program_roundtrip.py write joyai-llm-flash-l5 512 2>&1 | grep '^512' | cut -c1-700
  python3 tools/chip_probes/program_roundtrip.py read joyai-llm-flash-l5 512 2>&1 | grep '^512' | cut -c1-1500
  rc=${PIPESTATUS[0]}; echo "roundtrip rc=$rc"
  [ $rc -ne 0 ] && exit 1
  # the two claimed cells: a cold set-up alone a side, then three pairs of
  # whole runs, the order alternating, a seed a pair
  for cs in "$G 49000001" "$O 49000002"; do
    set -- $cs
    $S pr49.first change:$1:${2}01:0:SETUP_IN_CELL_STOP=1 \
       parent:$1:${2}02:0:SETUP_IN_CELL_STOP=1
    $S pr49.first parent:$1:${2}03:0 change:$1:${2}03:0 \
       change:$1:${2}04:0 parent:$1:${2}04:0 \
       parent:$1:${2}05:0 change:$1:${2}05:0
  done
  # the same work: parent = change compiled = change loaded.  Granite's store
  # stands since its cell ran; a PIO_* variable is in the store's key and not
  # in JAX's, so under one the change COMPILES (from JAX's cache) what it
  # would have loaded.  JoyAI's first change run compiles cold and fills the
  # store, the second loads
  bits pr49.first.bits granite-4.0-h-small-l10-ep2 change loaded
  bits pr49.first.bits granite-4.0-h-small-l10-ep2 change compiled PIO_PR49_PROBE=compile
  bits pr49.first.bits granite-4.0-h-small-l10-ep2 parent parent
  bits pr49.first.bits joyai-llm-flash-l5 change compiled
  bits pr49.first.bits joyai-llm-flash-l5 change loaded
  bits pr49.first.bits joyai-llm-flash-l5 parent parent
  compare_bits pr49.first.bits granite-4.0-h-small-l10-ep2 joyai-llm-flash-l5
  echo "bits rc=$?"
  verify first granite-4.0-h-small-l10-ep2 olmo-hybrid-7b-l16 joyai-llm-flash-l5
  ls -la ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs 2>/dev/null | tail -n +1 | awk '{n+=1; b+=$5} END {print "store entries+3:", n, "bytes:", b}'
  ;;
second)
  # the hot path first: two pairs of the ALS cell (p50 and p95 must not
  # move) after a cold set-up alone a side, a traced run of the change
  $S pr49.second change:$A:4900000301:0:SETUP_IN_CELL_STOP=1 \
     parent:$A:4900000302:0:SETUP_IN_CELL_STOP=1 \
     parent:$A:4900000303:0 change:$A:4900000303:0 \
     change:$A:4900000304:0 parent:$A:4900000304:0 \
     change:$A:4900000713:1
  verify second als-wgde-d128
  # the claimed cells again (their stores have not outlived the first call):
  # traced runs of the change with every rung loaded — the new metric must
  # read 100, `setup.branch_trace_share` nothing, the device metrics as
  # before — and the parent traced under this PR's benchmark files: its
  # line comes without the new metric and does not fail
  $S pr49.second change:$G:4900000701:0:SETUP_IN_CELL_STOP=1 change:$G:4900000711:1
  verify second granite-4.0-h-small-l10-ep2
  $S pr49.second change:$O:4900000702:0:SETUP_IN_CELL_STOP=1 change:$O:4900000712:1 \
     parent:$O:4900000712:1
  verify second olmo-hybrid-7b-l16
  $S pr49.second change:$J:4900000401:0:SETUP_IN_CELL_STOP=1
  verify second joyai-llm-flash-l5
  # a pair of each remaining cell, after a cold set-up alone a side
  for cs in "$W 49000005 trinity-large-l5-ep8" "$F 49000006 falcon-h1-34b-l6"; do
    set -- $cs
    $S pr49.second change:$1:${2}01:0:SETUP_IN_CELL_STOP=1 \
       parent:$1:${2}02:0:SETUP_IN_CELL_STOP=1 \
       parent:$1:${2}03:0 change:$1:${2}03:0
    verify second $3
  done
  ;;
third)
  # ISSUE 49's second worry, after the second call's traced ALS run read
  # `dispatch.launch_ms` 1.07 where the ledger reads 0.83-0.87: a loaded and
  # a compiled program of one rung take the same dispatch in turn, then a
  # traced pair and two more untraced pairs of the ALS cell
  ( cd .bench_archive/change && python3 $root/tools/chip_probes/loaded_call_cost.py \
      als-wgde-d128 --n 400 --rungs 1,8 ) 2>&1 | grep '^[0-9{]' | cut -c1-600
  mkdir -p chiprun_out; cp .bench_archive/change/chiprun_out/pr49.loaded_call_cost.* chiprun_out/ 2>/dev/null
  $S pr49.third change:$A:4900000901:0:SETUP_IN_CELL_STOP=1 \
     parent:$A:4900000911:1 change:$A:4900000911:1 \
     change:$A:4900000903:0 parent:$A:4900000903:0 \
     parent:$A:4900000904:0 change:$A:4900000904:0
  ;;
fourth)
  # the ALS cell's p95 read higher with the change in four of four pairs by
  # 1.0-3.3 % (p50 mixed): three more pairs, the store built first
  $S pr49.fourth change:$A:4900001001:0:SETUP_IN_CELL_STOP=1 \
     change:$A:4900001003:0 parent:$A:4900001003:0 \
     parent:$A:4900001004:0 change:$A:4900001004:0 \
     change:$A:4900001005:0 parent:$A:4900001005:0
  ;;
esac
