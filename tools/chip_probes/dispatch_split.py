"""Where does a slow dispatch sit: in the enqueue of the compiled score
program, in the wait for it, or in a host that was not running at all?

PR 25's dispatch records put the stall of `benchmark/findings/
stall.serve-burst.md` into the stage `device_compute` (1,553 ms once, 300-384
ms in one dispatch of a hundred, against 259).  That stage is two statements.
This probe deploys the benchmark's configuration as a run does (both copies
of the tables on the device), then calls the rung-16 program 600 times with
the host quiet and 600 times with 16 Python threads contending for the GIL (as
the HTTP handlers do), timing each statement apart, while a heartbeat thread
that sleeps 5 ms at a time records how late the host let it wake - and a
heartbeat in a child process that never loads JAX does the same, to tell a
pause of this process (the GIL, the runtime) from a pause of the whole
machine.  It also reads what the container says about CPU throttling.

    chiprun --timeout 1200 -- python tools/chip_probes/dispatch_split.py

Writes chiprun_out/dispatch_split.json; under JAX_PLATFORMS=cpu pass
`--shrink 500 --n 30` to check the script itself.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


CHILD = """
import sys, time
end = time.time() + float(sys.argv[1])
with open(sys.argv[2], "w") as f:
    while time.time() < end:
        f.write(repr(time.time()) + chr(10))
        time.sleep(0.005)
"""


def host_facts() -> dict:
    facts = {"affinity": len(os.sched_getaffinity(0))}
    for path in ("/sys/fs/cgroup/cpu.max", "/sys/fs/cgroup/cpu.stat",
                 "/sys/fs/cgroup/cpu/cpu.cfs_quota_us",
                 "/sys/fs/cgroup/cpu/cpu.stat", "/proc/pressure/cpu",
                 "/proc/loadavg"):
        try:
            with open(path) as f:
                facts[path] = f.read().strip()
        except OSError as e:
            facts[path] = type(e).__name__
    return facts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=600)
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--rung", type=int, default=16)
    args = ap.parse_args()

    import jax
    import numpy as np

    from pio_bench.engines import als_recommendation as family
    from predictionio_tpu.parallel import mesh as mesh_mod

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "als-wgde-d128.json")) as f:
        cfg = json.load(f)
    cfg["users"] //= args.shrink
    cfg["items"] //= args.shrink
    ctx = mesh_mod.MeshContext.create()
    workdir = tempfile.mkdtemp(prefix="pio_probe_")
    dep = family.Deployment(cfg, 2900000099, workdir, ctx)
    scorer = dep.scorer()
    fn, static = scorer._fns[args.rung], scorer._static_args
    rng = np.random.default_rng(25)

    beats: list = []
    halt = threading.Event()
    child_file = os.path.join(workdir, "child_beats.txt")
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD, str(2 * args.n * 0.4 + 30), child_file])
    facts_before = host_facts()
    off = time.time() - time.perf_counter()  # perf_counter -> unix

    def heartbeat():
        while not halt.is_set():
            beats.append(time.perf_counter())
            time.sleep(0.005)

    def contend():
        while not halt.is_set():
            json.dumps({"items": list(range(200))})
            time.sleep(0.001)

    threading.Thread(target=heartbeat, daemon=True).start()
    rows = []
    for phase, n_threads in (("quiet", 0), ("contended", 16)):
        for _ in range(n_threads):
            threading.Thread(target=contend, daemon=True).start()
        for i in range(args.n):
            users = rng.integers(0, cfg["users"], args.rung).astype(np.int32)
            t0 = time.perf_counter()
            u_dev = scorer._put_repl(users)
            t1 = time.perf_counter()
            outs = fn(*static, u_dev)
            t2 = time.perf_counter()
            jax.block_until_ready(outs)
            t3 = time.perf_counter()
            jax.device_get(outs)
            t4 = time.perf_counter()
            rows.append({"phase": phase, "i": i, "t0": t0,
                         "h2d_ms": (t1 - t0) * 1e3,
                         "enqueue_ms": (t2 - t1) * 1e3,
                         "wait_ms": (t3 - t2) * 1e3,
                         "d2h_ms": (t4 - t3) * 1e3, "t4": t4})
    halt.set()
    child.terminate()
    child.wait()
    with open(child_file) as f:
        cbeat = np.asarray([float(x) for x in f.read().split()]) - off
    beat = np.asarray(beats)
    for name, b in (("heartbeat_gap_ms", beat), ("child_gap_ms", cbeat)):
        gaps = np.diff(b)
        for r in rows:  # the longest silence of that heartbeat inside the dispatch
            inside = (b[1:] >= r["t0"]) & (b[:-1] <= r["t4"])
            r[name] = float(gaps[inside].max() * 1e3) if inside.any() else 0.0
    out = {"device": str(jax.devices()[0].device_kind), "rung": args.rung,
           "host_before": facts_before, "host_after": host_facts(),
           "summary": {}, "slow": [], "rows": rows}
    for phase in ("quiet", "contended"):
        rs = [r for r in rows if r["phase"] == phase]
        out["summary"][phase] = {
            k: {"p50": float(np.percentile([r[k] for r in rs], 50)),
                "p99": float(np.percentile([r[k] for r in rs], 99)),
                "max": float(max(r[k] for r in rs))}
            for k in ("h2d_ms", "enqueue_ms", "wait_ms", "d2h_ms",
                      "heartbeat_gap_ms", "child_gap_ms")}
    med = float(np.median([r["enqueue_ms"] + r["wait_ms"] for r in rows]))
    out["slow"] = [r for r in rows
                   if r["enqueue_ms"] + r["wait_ms"] > med + 30.0]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "dispatch_split.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps({"device": out["device"], "median_ms": med,
                      "host_before": out["host_before"],
                      "host_after": out["host_after"],
                      "summary": out["summary"]}))
    print(f"dispatches over median + 30 ms: {len(out['slow'])} of {len(rows)}")
    for r in out["slow"]:
        print({k: (round(v, 1) if isinstance(v, float) else v)
               for k, v in r.items() if k not in ("t0", "t4")})
    dep.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
