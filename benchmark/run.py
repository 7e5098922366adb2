#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the LAST line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``).  Everything else — counts, sample sizes,
the compile delta, each number compared beside its limit — goes on earlier
lines.  A cell names a configuration (``benchmark/configs/<name>.json``) and
a traffic mix (``benchmark/traffic/<name>.json``); per-layer metrics are
readers found by name in ``benchmark/metrics/``.

Exit codes: 0 a result was printed; 2 the program under test is not
importable from here; 3 JAX found no TPU, or fewer chips than the cell asks
for; 4 the cell, its configuration or its rate is not defined.  Codes 2-4
print no result.  ``--rehearse-cpu`` (with ``--shrink``) drives the same
command at a small size under ``JAX_PLATFORMS=cpu``: it says platform
``cpu`` and is never the result of a cell.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# -- the serving window -------------------------------------------------------


class TracePoller(threading.Thread):
    """Collects the server's finished request traces while the window runs
    (its ring holds 256; the generator marks every request as sampled)."""

    def __init__(self, dep, period_s: float = 0.2):
        super().__init__(daemon=True)
        self.dep, self.period_s = dep, period_s
        self.seen: dict = {}
        self._halt = threading.Event()

    def poll(self) -> None:
        got = self.dep.traces()
        for t in (got.get("traces", []) if isinstance(got, dict) else got):
            if str(t.get("requestId", "")).startswith("bench-"):
                self.seen[t["requestId"]] = t

    def run(self) -> None:
        while not self._halt.wait(self.period_s):
            self.poll()

    def finish(self) -> list:
        self._halt.set()
        self.join()
        self.poll()
        return list(self.seen.values())


class StallWatch(threading.Thread):
    """A builder's tool (``PIO_BENCH_STALL_DUMP=<file>``): when the batcher
    has held a run for two seconds without finishing a batch, write every
    thread's stack to the file, and again after six."""

    def __init__(self, batcher, path: str):
        super().__init__(daemon=True)
        self.batcher, self.path = batcher, path
        self._halt = threading.Event()

    def run(self) -> None:
        import faulthandler

        last, since, dumped = -1, time.perf_counter(), 0
        while not self._halt.wait(0.25):
            n = self.batcher._n_batches
            now = time.perf_counter()
            if n != last or not self.batcher._busy.locked():
                last, since, dumped = n, now, 0
            elif now - since > (2.0, 6.0, 1e9)[dumped]:
                dumped += 1
                with open(self.path, "a") as f:
                    f.write(f"\n=== no batch finished for {now - since:.2f} s "
                            f"(at {now - T_START:.2f} s of the run)\n")
                    f.flush()
                    faulthandler.dump_traceback(file=f, all_threads=True)

    def finish(self) -> None:
        self._halt.set()
        self.join()


def serve_window(dep, cfg, traffic, args, rate_rps, workdir):
    """Spawn the generator, time set-up, open the window, collect."""
    from pio_bench import schedule

    sched = schedule.build(traffic, cfg["users"], rate_rps, args.seconds,
                           args.seed)
    n = len(sched["due_s"])
    spec = {
        "host": "127.0.0.1", "port": dep.port, "path": "/queries.json",
        "due_s": sched["due_s"].tolist(),
        "user": [dep.user_name(u) for u in sched["user"]],
        "num": sched["num"].tolist(),
        "client_timeout_s": cfg["client_timeout_s"],
        "request_ids": bool(args.trace), "window_s": args.seconds,
        "warm_connections": 128,
    }
    spec_path = os.path.join(workdir, "loadgen_spec.json")
    out_path = os.path.join(workdir, "answers.jsonl")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "pio_bench", "loadgen.py"),
         spec_path, out_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    watch = None
    try:
        if proc.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not come up")
        ready_before = dep.readyz()
        counters_before = dep.counters()
        setup_s = time.perf_counter() - T_START
        say(f"set-up done in {setup_s:.3f} s; window: {n} requests due over "
            f"{args.seconds} s at {rate_rps:.3f} req/s")
        if os.environ.get("PIO_BENCH_STALL_DUMP"):
            watch = StallWatch(dep.qs._batcher,
                               os.environ["PIO_BENCH_STALL_DUMP"])
            watch.start()
        proc.stdin.write("go\n")
        proc.stdin.flush()
        t_go = time.perf_counter()
        traced = None
        if args.trace:
            traced = traced_slice(dep, args, workdir, t_go)
        proc.wait(timeout=args.seconds + cfg["client_timeout_s"] + 60)
        if proc.returncode != 0:
            raise RuntimeError(f"the load generator exited {proc.returncode}")
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        if watch is not None:
            watch.finish()
    counters_after = dep.counters()
    ready_after = dep.readyz()
    records = []
    with open(out_path) as f:
        header = json.loads(f.readline())
        for line in f:
            rec = json.loads(line)
            i = rec["i"]
            rec["user"] = int(sched["user"][i])
            rec["num"] = int(sched["num"][i])
            if rec["status"] == 200:
                try:
                    rec["answer"] = json.loads(rec.pop("body"))
                except ValueError:
                    rec["status"], rec["error"] = 0, "answer is not JSON"
                else:
                    rec["degraded"] = bool(
                        isinstance(rec["answer"], dict)
                        and rec["answer"].get("degraded"))
            records.append(rec)
    return {
        "records": records, "header": header, "setup_s": setup_s,
        "ready_before": ready_before, "ready_after": ready_after,
        "counters_before": counters_before, "counters_after": counters_after,
        "traced": traced,
    }


def traced_slice(dep, args, workdir, t_go):
    """Profile a few seconds in the middle of the window and collect the
    server's request traces for all of it."""
    import jax

    from pio_bench import xplane

    poller = TracePoller(dep)
    poller.start()
    slice_s = min(3.0, args.seconds / 3.0)
    start_at = min(args.seconds / 3.0, max(0.0, args.seconds - slice_s))
    time.sleep(max(0.0, t_go + start_at - time.perf_counter()))
    trace_dir = os.path.join(workdir, "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    c0 = dep.counters()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    time.sleep(slice_s)
    jax.profiler.stop_trace()
    wall = time.perf_counter() - t0
    c1 = dep.counters()
    time.sleep(max(0.0, t_go + args.seconds - time.perf_counter()))
    reduced = xplane.reduce_dir(
        trace_dir, wall,
        xplane.DEVICE_PREFIX if jax.devices()[0].platform == "tpu"
        else "/host:CPU")  # a CPU rehearsal has no device plane
    reduced["dispatches"] = (
        c1.get("fastpath.calls", 0) - c0.get("fastpath.calls", 0))
    reduced["trace_dir"] = trace_dir
    return {"device": reduced, "poller": poller}


def run_serving(cfg, traffic, args, bench, workload, ctx, device):
    from pio_bench.readers import (answered, lateness_ms, latencies_ms,
                                   longest_silence, pct, peak_inflight)

    family = importlib.import_module("pio_bench.engines." + cfg["engine"])
    rate = args.rate
    if rate is None:
        if not cfg.get("knee_rps"):
            print(f"run.py: configuration {cfg['name']} has no knee_rps; "
                  "find it with benchmark/sweep.py", file=sys.stderr)
            return 4
        rate = cfg["knee_rps"] * traffic["rate_fraction_of_knee"]
    workdir = tempfile.mkdtemp(prefix="pio_bench_")
    dep = None
    try:
        dep = family.Deployment(cfg, args.seed, workdir, ctx)
        say(f"deployed {cfg['name']} as {dep.instance_id} on {dep.base}; "
            f"seconds {dep.seconds}")
        win = serve_window(dep, cfg, traffic, args, rate, workdir)
        traces = (win["traced"]["poller"].finish() if win["traced"] else [])
        records = win["records"]
        good = answered(records)
        failed = len(records) - len(good)
        by_status: dict = {}
        for r in records:
            key = ("degraded" if r.get("degraded") else
                   r.get("error", "").split(":")[0] or str(r["status"]))
            by_status[key] = by_status.get(key, 0) + 1
        say(f"requests: {len(records)} due, {len(good)} answered, {failed} "
            f"failed {by_status}; connections opened "
            f"{win['header']['connections_opened']}; peak in flight "
            f"{peak_inflight(records)} (the server sheds at its max_inflight)"
            "; longest silence between answers %.3f s, ending at %.2f s"
            % longest_silence(records))
        keep = os.environ.get("PIO_BENCH_KEEP_RECORDS")
        if keep:  # a builder's look at one window's timeline; not a run's output
            with open(keep, "w") as f:
                json.dump([[r["i"], r["due"], r.get("sent"), r["done"],
                            r["status"]] for r in records], f)
        t_audit = time.perf_counter()
        verdict = family.audit(dep, records, sample=200)
        say(f"audit took {time.perf_counter() - t_audit:.2f} s (not set-up), "
            f"of which the float64 sweep {verdict['seconds']}")
        problems = (family.ready_problems(win["ready_before"], dep.instance_id)
                    + family.ready_problems(win["ready_after"], dep.instance_id))
        cb, ca = win["counters_before"], win["counters_after"]
        compile_delta = (ca.get("fastpath.compile_count", 0)
                         - cb.get("fastpath.compile_count", 0))
        say(f"compile_count delta in the window: {compile_delta} (limit 0)")
        if compile_delta:
            problems.append("a program compiled inside the window")
        for name in ("score", "beat", "order"):
            say(f"check {name}_over_tol = {verdict[name + '_over_tol']:.6g} "
                f"(limit 1; tolerance {verdict['tolerance']:g}*|u|*max|v|) "
                f"over {verdict['served_rows']} served + "
                f"{verdict['rung_rows']} direct rung rows")
        say(f"check structural failures = {verdict['n_structural_failures']} "
            f"(limit 0) over {verdict['answers_checked_structurally']} "
            f"answers {verdict['structural_failures']}")
        say(f"check readiness/compile problems = {problems} (limit none)")
        correct = bool(verdict["ok"] and not problems)
        lat_ms = latencies_ms(good)
        say(f"latency samples {len(lat_ms)}; generator lateness p95 "
            f"{pct(lateness_ms(records), 95)} ms")
        if lat_ms:
            say("latency ms: mean %.1f  p25 %.1f  p50 %.1f  p75 %.1f  p90 %.1f"
                "  p95 %.1f  max %.1f" % (
                    sum(lat_ms) / len(lat_ms),
                    *(pct(lat_ms, q) for q in (25, 50, 75, 90, 95)),
                    max(lat_ms)))
            hits = {k: ca["fastpath.bucket_hits"][k]
                    - cb["fastpath.bucket_hits"].get(k, 0)
                    for k in ca.get("fastpath.bucket_hits", {})}
            say(f"dispatches by rung in the window: {hits}")

        def reports(m) -> bool:  # a metric without the key is every cell's
            return workload["name"] in m.get("workloads", [workload["name"]])

        breakdown = None
        if args.trace:
            dev_red = win["traced"]["device"]
            say(f"request traces collected: {len(traces)}; traced slice "
                f"{dev_red['window_s']:.3f} s, {dev_red['dispatches']} "
                f"dispatches, device busy {dev_red['busy_s']:.4f} s")
            metrics = per_layer_metrics(
                [m for m in bench["per_layer"] if reports(m)], {
                    "cfg": cfg, "traffic": traffic, "records": records,
                    "good": good, "traces": traces, "counters_before": cb,
                    "counters_after": ca, "device_trace": dev_red,
                    "window_s": args.seconds}, device)
            device["busy_s"] = dev_red["busy_s"]
            device["window_s"] = dev_red["window_s"]
            breakdown = {"device_ops": dev_red["top_ops"][:10],
                         "idle_gaps": dev_red["idle_gaps"][:10]}
            keep = os.environ.get("PIO_BENCH_KEEP_TRACE")
            if keep:  # a builder's look at one real trace; not a run's output
                shutil.copytree(dev_red["trace_dir"], keep, dirs_exist_ok=True)
        else:
            e2e = {"serve.p50_ms": pct(lat_ms, 50),
                   "serve.p95_ms": pct(lat_ms, 95),
                   "setup_s": win["setup_s"]}
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in bench["end_to_end"]
                       if reports(m) and e2e.get(m["name"]) is not None}
        ms = [d.memory_stats() or {} for d in ctx.mesh.devices.flat]
        device["memory_peak_bytes"] = max(
            int(m.get("peak_bytes_in_use", 0)) for m in ms)
        result = {"correct": correct, "attempted": len(records),
                  "failed": failed, "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        return result
    finally:
        if dep is not None:
            dep.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def per_layer_metrics(declared, rctx, device) -> dict:
    """Each declared per-layer metric through its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    from pio_bench import costs, peaks
    from pio_bench.readers import load_reader

    # a CPU rehearsal borrows the v5e row to exercise the readers
    rctx["peaks"] = (peaks.for_kind(device["kind"])
                     if device["platform"] == "tpu"
                     else peaks.PEAKS["TPU v5 lite"])
    rctx["costs"] = costs
    out = {}
    for m in declared:
        value = load_reader(m["name"])(rctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


DRIVERS = {"serve_open_loop": run_serving}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered rate override (sweeps and rehearsals only)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide users and items (with --rehearse-cpu only)")
    args = ap.parse_args()

    try:
        bench = load_json(ROOT, "BENCHMARK.json")
    except OSError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 4
    workload = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 4
    entry = next(c for c in bench["configs"] if c["name"] == workload["config"])
    cfg = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", workload["traffic"] + ".json")
    if args.shrink != 1:
        if not args.rehearse_cpu:
            print("run.py: --shrink is for --rehearse-cpu", file=sys.stderr)
            return 4
        cfg["users"] //= args.shrink
        cfg["items"] //= args.shrink

    try:
        sys.path.insert(0, ROOT)
        import predictionio_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program under test is not importable from {ROOT}: "
              f"{e}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    rehearsal = args.rehearse_cpu and device["platform"] == "cpu"
    if not rehearsal and (device["platform"] != "tpu"
                          or len(devs) < workload["chips"]):
        print(f"run.py: cell {workload['name']} needs {workload['chips']} TPU "
              f"chip(s); JAX found {device}", file=sys.stderr)
        return 3
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"count: {device['count']}" + ("  REHEARSAL, never a result"
                                       if rehearsal else ""))

    from predictionio_tpu.parallel import mesh as mesh_mod

    # the program places the compile cache itself: JAX_COMPILATION_CACHE_DIR
    # if set, else the fixed <checkout>/.jax_compile_cache
    ctx = mesh_mod.MeshContext.create()
    driver = DRIVERS.get(traffic.get("kind"))
    if driver is None:
        print(f"run.py: no driver for traffic kind {traffic.get('kind')!r}",
              file=sys.stderr)
        return 4
    result = driver(cfg, traffic, args, bench, workload, ctx, device)
    if isinstance(result, int):
        return result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
