#!/bin/bash
# ISSUE 40's measurement of one cell in one call: untraced pairs (parent,
# change, change, parent, ...) for the end-to-end metrics, then traced pairs
# for the per-layer ones, through pr37_cell.sh (TAG=pr40), the traced runs
# keeping their slices; then, on the machine that made them, the device-side
# gap between consecutive programs of every kept slice
# (`turnaround_split.py --device-gap`) and what each run's `GET /` said of the
# mechanism (the batcher's `ahead_*` and estimates, the scorer's
# `held_launches`, peak memory).  The slices are then dropped: only the
# numbers come back.
#   chiprun --timeout 3000 -- bash tools/chip_probes/pr40_cell.sh <cell> <parent dir> <first seed> <untraced pairs> <traced pairs>
# CHANGE names the change's unpacked archive (pairs.sh).
cell=$1; tag=${TAG:-pr40}
KEEP_TRACES=1 TAG=$tag bash tools/chip_probes/pr37_cell.sh "$@"
for trace in chiprun_out/$tag.$cell.traced/*.trace; do
  [ -d $trace ] || continue
  python3 tools/chip_probes/turnaround_split.py --device-gap $trace >> chiprun_out/$tag.$cell.device_gap.jsonl
  rm -rf $trace
done
[ -f chiprun_out/$tag.$cell.device_gap.jsonl ] && cat chiprun_out/$tag.$cell.device_gap.jsonl
python3 - $cell $tag <<'PY'
import glob, json, sys
cell, tag = sys.argv[1:3]
keys = ("batches", "inline_batches", "ahead_batches", "ahead_missed",
        "launch_run_ms", "launch_lead_ms", "rung_run_ms", "run_gap_ms",
        "turnaround_ms_sum", "turnaround_n", "slow_dispatches")
for path in sorted(glob.glob(f"chiprun_out/{tag}.{cell}.*/*.rings.json")):
    doc = json.load(open(path))
    root = doc.get("root", {})
    b = root.get("batching") or {}
    fp = root.get("fastpath") or [{}]
    fp = fp[0] if isinstance(fp, list) else fp
    ahead = [r["aheadMs"] for r in doc["dispatches"]
             if r.get("aheadMs") is not None]
    ahead.sort()
    print(json.dumps({
        "run": path.split("/", 1)[1],
        **{k: b.get(k) for k in keys if k in b},
        "held_launches": (fp or {}).get("held_launches"),
        "launch_lag_ms": (fp or {}).get("launch_lag_ms"),
        "ring_ahead_ms_median": ahead[len(ahead) // 2] if ahead else None,
        "ring_behind_a_run": len(ahead), "ring": len(doc["dispatches"]),
    }))
PY
