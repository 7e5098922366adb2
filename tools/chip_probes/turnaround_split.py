"""What is left of the batcher's turnaround, by stage (ISSUE 33).

Reads ring files that `serve_rings.py` kept (`GET /trace/dispatches.json`)
and, for every dispatch that followed a run which ended with rows waiting,
splits the time between that run's device program returning and this one's
launch into the stages the records hold: the earlier run's `d2h`,
`postprocess` and `resolve`, the hand-off (its end -> this run's start, on
the one clock both records share), and this run's `batch_assembly` and
`h2d`.  `collect` (first row taken -> run starts) is printed beside it for
the worker's dispatches.  Needs no chip:

    python3 tools/chip_probes/turnaround_split.py <rings.json>...
"""
import json
import statistics
import sys


def quartiles(values):
    if len(values) < 2:
        return [round(v, 3) for v in values]
    return [round(q, 3) for q in statistics.quantiles(values, n=4)]


def split(path):
    with open(path) as f:
        recs = sorted(json.load(f)["dispatches"], key=lambda r: r["seq"])
    parts = {k: [] for k in ("d2h", "postprocess", "resolve", "hand_off",
                             "batch_assembly", "h2d", "sum", "collect")}
    after = {"worker": [], "inline": []}
    for prev, rec in zip(recs, recs[1:]):
        if rec["seq"] != prev["seq"] + 1 or not prev["depthAtEnd"]:
            continue
        st, pst = rec["stagesMs"], prev["stagesMs"]
        t_run = rec["startMonotonic"] * 1e3 + st["collect"]
        hand_off = t_run - (prev["startMonotonic"] * 1e3 + prev["wallMs"])
        row = {"d2h": pst["d2h"], "postprocess": pst["postprocess"],
               "resolve": pst["resolve"], "hand_off": hand_off,
               "batch_assembly": st["batch_assembly"], "h2d": st["h2d"]}
        row["sum"] = sum(row.values())
        if not rec["inline"]:
            row["collect"] = st["collect"]
            after["inline" if prev["inline"] else "worker"].append(hand_off)
        for k, v in row.items():
            parts[k].append(v)
    return {
        "file": path, "dispatches": len(recs),
        "followed_a_run_that_left_rows": len(parts["sum"]),
        "of_them_inline": len(parts["sum"]) - len(parts["collect"]),
        "quartiles_ms": {k: quartiles(v) for k, v in parts.items()},
        "hand_off_after_a_worker_run_ms": quartiles(after["worker"]),
        "hand_off_after_an_inline_run_ms": quartiles(after["inline"]),
        "n_after": {k: len(v) for k, v in after.items()},
    }


if __name__ == "__main__":
    for name in sys.argv[1:]:
        print(json.dumps(split(name)))
