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

ISSUE 40 (launch-ahead) asks the question from the DEVICE's side: how long
does the chip stand idle between two consecutive programs, with and
without a row waiting?  `--device-gap` reads kept traces of traced runs
(`PIO_BENCH_KEEP_TRACE`), joins every `pio.device_compute` span to its
programs as the benchmark does (`benchmark/pio_bench/hostjoin.py`, the
device clock shifted as there) and, for every two dispatches consecutive by
`seq`, takes (the later one's first program event) - (the earlier one's
last), by what the trace says of the later dispatch:

* `ahead`: its `pio.device_compute` span began before the earlier one's
  ended — its program was enqueued behind the one in flight;
* `waited`: not ahead, but the `pio.collect` span that led to it began
  before the earlier span's end — a row was in a worker's hand while the
  earlier program ran, and the device then waited for the host;
* `free`: nothing waited; the gap is the traffic's own.

On a parent of ISSUE 40 nothing is `ahead`, and `waited` is what that PR
set out to remove.  Needs a trace a TPU made (a CPU's has no device plane):

    python3 tools/chip_probes/turnaround_split.py --device-gap <trace dir>...
"""
import json
import os
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


def device_gaps(trace_dir):
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "benchmark"))
    from pio_bench import hostjoin, xplane

    planes = hostjoin.load_planes(xplane.find(trace_dir))
    joined = hostjoin.join(planes)
    collects = sorted((s, e) for n, s, e, _ in planes["spans"]
                      if n == "pio.collect")
    by_seq = {int(d["seq"]): d for d in joined["dispatches"]
              if d["seq"] is not None}
    gaps = {"ahead": [], "waited": [], "free": []}
    ahead_by = []
    for seq, later in sorted(by_seq.items()):
        earlier = by_seq.get(seq - 1)
        if earlier is None:
            continue
        kind = "free"
        if later["span"][0] < earlier["span"][1]:
            kind = "ahead"
            ahead_by.append((earlier["span"][1] - later["span"][0]) / 1e6)
        else:
            # the collect that ended last before this span began
            led = [c for c in collects if c[1] <= later["span"][0]]
            if led and earlier["span"][0] < led[-1][1] and (
                    led[-1][0] < earlier["span"][1]):
                kind = "waited"
        gaps[kind].append((later["first"] - earlier["last"]) / 1e6)
    return {
        "trace": trace_dir, "dispatches_joined": len(by_seq),
        "clock_shift_ms": joined["clock_shift_ns"] / 1e6,
        "contained_shifted": joined["contained_shifted"],
        "n": {k: len(v) for k, v in gaps.items()},
        "device_gap_ms_quartiles": {k: quartiles(v) for k, v in gaps.items()},
        # of the ahead ones: how long before the earlier span's end the
        # later span began, and how many programs still started late
        "span_ahead_by_ms_quartiles": quartiles(ahead_by),
        "ahead_with_gap_over_0.3ms": sum(g > 0.3 for g in gaps["ahead"]),
    }


if __name__ == "__main__":
    if sys.argv[1:2] == ["--device-gap"]:
        for name in sys.argv[2:]:
            print(json.dumps(device_gaps(name)))
    else:
        for name in sys.argv[1:]:
            print(json.dumps(split(name)))
