#!/bin/bash
# ISSUE 37's measurement of one cell in one call: untraced pairs (parent,
# change, change, parent, ...) for the end-to-end metrics, then traced pairs
# for the per-layer ones.  The parent is an unpacked `git archive` of the
# parent commit with this PR's BENCHMARK.json and benchmark/ laid over it, as
# the driver does, so that its traced runs print the new dispatch.* metrics.
#   chiprun --timeout 3000 -- bash tools/chip_probes/pr37_cell.sh <cell> <parent dir> <first seed> <untraced pairs> <traced pairs>
# TAG names the outputs (chiprun_out/$TAG.<cell>.{pairs,traced}.jsonl; PR 38
# ran it with TAG=pr38), CHANGE the change's unpacked archive (pairs.sh).
cell=$1; parent=$2; first=$3; n0=$4; n1=$5; tag=${TAG:-pr37}
[ $n0 -gt 0 ] && bash tools/chip_probes/pairs.sh $tag.$cell.pairs $parent $cell 40 0 $(seq $first $((first + n0 - 1)))
[ $n1 -gt 0 ] && bash tools/chip_probes/pairs.sh $tag.$cell.traced $parent $cell 40 1 $(seq $((first + 50)) $((first + 50 + n1 - 1)))
python3 - $cell $tag <<'PY'
import json, os, sys
cell, name = sys.argv[1:3]
for tag in ("pairs", "traced"):
    if not os.path.exists(f"chiprun_out/{name}.{cell}.{tag}.jsonl"):
        continue
    for line in open(f"chiprun_out/{name}.{cell}.{tag}.jsonl"):
        r = json.loads(line)
        res = r["result"]
        m = {k: round(v["value"], 4) for k, v in res["metrics"].items()}
        print(tag, r["side"], r["seed"], "rc", r["rc"], "correct", res["correct"], "failed", res["failed"], json.dumps(m))
PY
