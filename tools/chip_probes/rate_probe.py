"""What the batcher's cut does across offered rates (ISSUE 28).

One deployment of a benchmark configuration, then the traffic mix at each
rate in turn, every window a new generator process and a new seed, as
`benchmark/sweep.py` does; kept beside each window's latencies are the
batcher's own counters over it (rows a dispatch, inline / carried /
rounded-up shares, padded rows) and the fast path's dispatches by rung, and
at the end the estimates the cut decided from.  Run from the root of the
checkout to be measured, so the parent's unpacked archive is driven by this
copy of the script:

    cd <checkout> && python3 <repo>/tools/chip_probes/rate_probe.py <out.json> \
        --config als-wgde-d128 --steady 58.5 --steady-seeds 6 \
        --steady-seconds 40 --rates 150,300,600,1000 --seconds 15

A key the checkout's batcher does not have (the parent's
`rounded_up_batches`) reads null.  Not part of any run of the benchmark.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import types

BENCH = os.path.join(os.getcwd(), "benchmark")
sys.path[:0] = [BENCH, os.getcwd()]  # run.py, and the program


def share(part, whole):
    return None if part is None or not whole else 100.0 * part / whole


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--config", default="als-wgde-d128")
    ap.add_argument("--traffic", default="serve-steady")
    ap.add_argument("--steady", type=float, default=58.5)
    ap.add_argument("--steady-seeds", type=int, default=6)
    ap.add_argument("--steady-seconds", type=float, default=40.0)
    ap.add_argument("--rates", default="150,300,600,1000")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2_800_000_001)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--shrink", type=int, default=1)
    a = ap.parse_args()

    import jax

    import run as bench_run
    from pio_bench.readers import (answered, delta, lateness_ms, latencies_ms,
                                   longest_silence, pct, peak_inflight)
    from sweep import backlog_at
    from predictionio_tpu.parallel import mesh as mesh_mod

    cfg = bench_run.load_json(BENCH, "configs", a.config + ".json")
    traffic = bench_run.load_json(BENCH, "traffic", a.traffic + ".json")
    cfg["users"] //= a.shrink
    cfg["items"] //= a.shrink
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse_cpu:
        print("rate_probe: no TPU", file=sys.stderr)
        return 3
    windows = [(a.steady, a.steady_seconds)] * a.steady_seeds + [
        (float(r), a.seconds) for r in a.rates.split(",") if r]
    ctx = mesh_mod.MeshContext.create()
    family = importlib.import_module("pio_bench.engines." + cfg["engine"])
    workdir = tempfile.mkdtemp(prefix="pio_rate_probe_")
    dep = family.Deployment(cfg, a.seed, workdir, ctx)
    rows = []
    try:
        for j, (rate, seconds) in enumerate(windows):
            args = types.SimpleNamespace(
                seed=a.seed + 1 + j, seconds=seconds, trace=0)
            win = bench_run.serve_window(dep, cfg, traffic, args, rate, workdir)
            recs = win["records"]
            good = answered(recs)
            lat = latencies_ms(good)
            batches = delta(win, "batcher.batches")
            queries = delta(win, "batcher.queries")
            row = {
                "rate_rps": rate, "seconds": seconds, "seed": args.seed,
                "due": len(recs), "answered": len(good),
                "failed": len(recs) - len(good),
                "p50_ms": pct(lat, 50), "p95_ms": pct(lat, 95),
                "batch_rows": queries / batches if batches else None,
                "batches": batches,
                "inline_share": share(
                    delta(win, "batcher.inline_batches"), batches),
                "carried_share": share(
                    delta(win, "batcher.carried_rows"), queries),
                "rounded_up_batches": delta(win, "batcher.rounded_up_batches"),
                "padded_rows": delta(win, "batcher.padded_rows"),
                "joined_rows": delta(win, "batcher.joined_rows"),
                "turnaround_ms": (
                    delta(win, "batcher.turnaround_ms_sum") / n
                    if (n := delta(win, "batcher.turnaround_n")) else None),
                "dispatches_by_rung": delta(
                    win, "fastpath.bucket_hits", sub=True),
                "backlog_mid": backlog_at(recs, seconds / 2),
                "backlog_end": backlog_at(recs, seconds),
                "late_p95_ms": pct(lateness_ms(recs), 95),
                "peak_inflight": peak_inflight(recs),
                "longest_silence_s": longest_silence(recs)[0],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
        batching = dep.root().get("batching") or {}
    finally:
        dep.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    out = {"config": a.config, "traffic": a.traffic,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "rows": rows,
           "rung_run_ms": batching.get("rung_run_ms"),
           "run_gap_ms": batching.get("run_gap_ms")}
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
