#!/bin/bash
# PR 42's second look at the window cell, one call: untraced pairs (parent,
# change, change, parent, ...) and then traced pairs, every run through
# setup_in_cell.py, which keeps the packed scorer's set-up phase by phase and
# the compile cache's state beside the run's result (REVIEW 42: `setup_s`
# rose by 2-3 s and the cause was not found).  The first run of each side may
# find its programs uncompiled: its `setup_s` is a cold one, its window is a
# window like any other.
#   chiprun --timeout 3000 -- bash tools/chip_probes/pr42_review.sh <tag> <parent dir> <change dir> <first seed> <untraced pairs> <traced pairs>
# Both dirs are unpacked archives (the parent's with this PR's BENCHMARK.json
# and benchmark/ laid over it).  Results: chiprun_out/<tag>.{pairs,traced}.jsonl,
# logs and each run's set-up JSON under chiprun_out/<tag>/.
tag=$1; parent=$(cd $2 && pwd); change=$(cd $3 && pwd); first=$4; n0=$5; n1=$6
cell=trinity-large-l5.serve-steady
root=$(pwd); mkdir -p $root/chiprun_out/$tag
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset} JAX_COMPILATION_CACHE_MAX_SIZE=${JAX_COMPILATION_CACHE_MAX_SIZE:-unset}"
[ -n "$JAX_COMPILATION_CACHE_DIR" ] && du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null
k=0
pairs() {  # <kind> <trace> <seed>...
  kind=$1; trace=$2; shift 2
  for seed in "$@"; do
    if [ $((k % 2)) = 0 ]; then order="parent change"; else order="change parent"; fi
    k=$((k + 1))
    for side in $order; do
      if [ $side = parent ]; then dir=$parent; else dir=$change; fi
      log=$root/chiprun_out/$tag/$side.seed$seed.log
      ( cd $dir && python3 $root/tools/chip_probes/setup_in_cell.py \
          $root/chiprun_out/$tag/$side.seed$seed.setup.json \
          --workload $cell --seed $seed --seconds 40 --trace $trace ) > $log 2>&1
      rc=$?
      echo "{\"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"result\": $(tail -1 $log)}" >> $root/chiprun_out/$tag.$kind.jsonl
      grep -E "deployed|set-up done|requests:|latency ms|by rung" $log | sed "s/^/$side seed$seed /" | cut -c1-330
      [ -n "$JAX_COMPILATION_CACHE_DIR" ] && du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null
    done
  done
}
[ $n0 -gt 0 ] && pairs pairs 0 $(seq $first $((first + n0 - 1)))
[ $n1 -gt 0 ] && pairs traced 1 $(seq $((first + 50)) $((first + 50 + n1 - 1)))
python3 - $tag <<'PY'
import glob, json, sys
tag = sys.argv[1]
for kind in ("pairs", "traced"):
    try:
        lines = open(f"chiprun_out/{tag}.{kind}.jsonl").readlines()
    except OSError:
        continue
    for line in lines:
        r = json.loads(line)
        m = {k: round(v["value"], 3) for k, v in r["result"]["metrics"].items()}
        print(kind, r["side"], r["seed"], "rc", r["rc"], "correct", r["result"]["correct"], "failed", r["result"]["failed"], json.dumps(m))
for path in sorted(glob.glob(f"chiprun_out/{tag}/*.setup.json")):
    d = json.load(open(path))
    ph = {}
    for p in d["phases"]:
        ph[p["name"]] = ph.get(p["name"], 0.0) + p["s"]
    ev = {}
    for e in d["events"]:
        ev[e["name"].rsplit("/", 1)[-1]] = ev.get(e["name"].rsplit("/", 1)[-1], 0.0) + e["s"]
    ca, cb = d.get("cache_after") or {}, d.get("cache_before") or {}
    print(path.split("/")[-1], json.dumps({
        "seconds": {k: round(v, 2) for k, v in d.get("seconds", {}).items()},
        "phases": {k: round(v, 2) for k, v in ph.items()},
        "events": {k: round(v, 2) for k, v in ev.items()},
        "counts": {k.rsplit("/", 1)[-1]: v for k, v in d["counts"].items()},
        "cache_mb": [round(cb.get("bytes", 0) / 1e6, 1), round(ca.get("bytes", 0) / 1e6, 1)],
        "cache_entries": [cb.get("entries"), ca.get("entries")],
        "written": [(r["name"][:28], round(r["bytes"] / 1e6, 1)) for r in ca.get("large", []) if r["written_by_this_run"]],
    }))
PY
