#!/bin/bash
# PR 48: the bit-equality check of ISSUE 48's "same work", one call: both
# packed families whose depth is a Python loop, every rung, parent against
# change (unpacked archives under .bench_archive/, as pr48_setup.sh's).
#   chiprun --timeout 1500 -- bash tools/chip_probes/pr48_bits.sh <tag> [seed]
# Exit 1 if any output of any rung differs, or a side compiled or warmed
# another number of programs.  Results: chiprun_out/<tag>.bits.json and each
# side's file under chiprun_out/<tag>/.
tag=$1; seed=${2:-4800000201}
root=$(pwd); mkdir -p $root/chiprun_out/$tag
for config in joyai-llm-flash-l5 trinity-large-l5-ep8; do
  for side in parent change; do
    ( cd $root/.bench_archive/$side && python3 $root/tools/chip_probes/branch_bits.py \
        $root/chiprun_out/$tag/bits.$config.$side.json $config $seed ) \
      > $root/chiprun_out/$tag/bits.$config.$side.log 2>&1
    echo "$config $side rc=$? $(tail -1 $root/chiprun_out/$tag/bits.$config.$side.log | cut -c1-300)"
  done
done
python3 - $tag <<'PY'
import json, sys
tag, bad, doc = sys.argv[1], 0, {}
for config in ("joyai-llm-flash-l5", "trinity-large-l5-ep8"):
    p, c = (json.load(open(f"chiprun_out/{tag}/bits.{config}.{side}.json"))
            for side in ("parent", "change"))
    differ = [f"{t}.{k}" for t in p["rungs"] for k in p["rungs"][t]["outputs"]
              if p["rungs"][t]["outputs"][k] != c["rungs"][t]["outputs"].get(k)]
    same_counts = all(p[k] == c[k] for k in ("compile_count", "warmup_executions"))
    doc[config] = {
        "device_kind": c["device_kind"], "seed": c["seed"],
        "compile_count": [p["compile_count"], c["compile_count"]],
        "warmup_executions": [p["warmup_executions"], c["warmup_executions"]],
        "rungs": list(p["rungs"]), "outputs_compared": sum(
            len(r["outputs"]) for r in p["rungs"].values()),
        "tokens": {t: r["tokens"] for t, r in p["rungs"].items()},
        "differ": differ}
    bad += bool(differ) or not same_counts or set(p["rungs"]) != set(c["rungs"])
    print(config, json.dumps(doc[config]))
json.dump(doc, open(f"chiprun_out/{tag}.bits.json", "w"), indent=1)
sys.exit(1 if bad else 0)
PY
