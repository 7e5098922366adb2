#!/usr/bin/env python3
"""Find a serving configuration's knee, once, on the chip.

    chiprun -- python3 benchmark/sweep.py --config als-wgde-d128 \
        --traffic serve-steady --rates 40,50,62.5,78,98,122 --seconds 15

Deploys the configuration once and offers the traffic mix at each rate in
turn (a new generator process and a new seed per rate).  The knee is the
highest rate at which the backlog does not grow: the requests due but not
yet answered at the window's end exceed those at its midpoint by no more
than a tenth of the second half's arrivals, and nothing failed.  Not part of
any run; the result goes into the configuration's ``knee_rps`` by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def backlog_at(records, t: float) -> int:
    due = sum(1 for r in records if r["due"] < t)
    done = sum(1 for r in records if r["done"] < t)
    return due - done


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="serve-steady")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2_900_000_001)
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--shrink", type=int, default=1)
    a = ap.parse_args()

    import importlib

    import jax

    import run as bench_run
    from pio_bench.readers import (answered, lateness_ms, latencies_ms,
                                   longest_silence, pct, peak_inflight)
    from predictionio_tpu.parallel import mesh as mesh_mod

    cfg = bench_run.load_json(HERE, "configs", a.config + ".json")
    traffic = bench_run.load_json(HERE, "traffic", a.traffic + ".json")
    cfg["users"] //= a.shrink
    cfg["items"] //= a.shrink
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse_cpu:
        print("sweep: no TPU", file=sys.stderr)
        return 3
    ctx = mesh_mod.MeshContext.create()
    family = importlib.import_module("pio_bench.engines." + cfg["engine"])
    workdir = tempfile.mkdtemp(prefix="pio_sweep_")
    dep = family.Deployment(cfg, a.seed, workdir, ctx)
    rows = []
    try:
        for j, rate in enumerate(float(x) for x in a.rates.split(",")):
            args = types.SimpleNamespace(
                seed=a.seed + 1 + j, seconds=a.seconds, trace=0)
            win = bench_run.serve_window(dep, cfg, traffic, args, rate, workdir)
            recs = win["records"]
            good = answered(recs)
            lat = latencies_ms(good)
            first_half = latencies_ms(
                [r for r in good if r["due"] < a.seconds / 2])
            mid, end = backlog_at(recs, a.seconds / 2), backlog_at(recs, a.seconds)
            half = sum(1 for r in recs if r["due"] >= a.seconds / 2)
            cb, ca = win["counters_before"], win["counters_after"]
            hits = {k: ca["fastpath.bucket_hits"][k]
                    - cb["fastpath.bucket_hits"][k]
                    for k in ca["fastpath.bucket_hits"]}
            row = {
                "rate_rps": rate, "due": len(recs), "answered": len(good),
                "failed": len(recs) - len(good),
                "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
                "p50_ms_first_half": pct(first_half, 50),
                "p95_ms_first_half": pct(first_half, 95),
                "backlog_mid": mid, "backlog_end": end,
                "second_half_arrivals": half,
                "sustained": bool(len(good) == len(recs)
                                  and end - mid <= 0.1 * half),
                "bucket_hits": hits,
                "late_p95_ms": pct(lateness_ms(recs), 95),
                "peak_inflight": peak_inflight(recs),
                "longest_silence_s": longest_silence(recs)[0],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        dep.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    out = {"config": a.config, "traffic": a.traffic, "seconds": a.seconds,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": rows, "knee_rps": max(sustained) if sustained else None}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep.{a.config}.{a.traffic}{a.tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_rps": out["knee_rps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
