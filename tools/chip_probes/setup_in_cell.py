"""One run of a benchmark cell, exactly as `benchmark/run.py` makes it, with
the packed scorer's set-up taken apart INSIDE the cell (PR 42: the window
cell's `setup_s` rose by 2-3 s with its top four rungs in token tiles, and a
scorer built by hand outside the cell did not show it).  Kept beside the
run's result, as JSON:

* `env`: where JAX's persistent compile cache lies and what bounds it (the
  variables the machine sets, and `jax.config`'s own);
* `cache_before` / `cache_after`: the cache directory's entries (name, bytes,
  written by this run or found); `store_before` / `store_after`: the same of
  the program store's directory beside it (PR 49; `programs_loaded` is in
  `root`, the scorer's counters);
* `phases`: wall seconds of `QueryServer.__init__`, the scorer's `__init__`,
  each rung made ready (`compile.<rung>`: the scorer's `_compile` on a tree
  before PR 49, `RungPrograms._ready` since, a load from the program store
  or trace + lowering + compile; `store.load` / `store.save` inside it),
  `program_bytes` of each rung, each rung's warm-up
  run (`_warm` is replaced by a copy of itself that times them),
  `measure_lag`, `QueryServer.start`, each with its offset from the process's
  start;
* `events`: every duration JAX's own monitoring reports (a program's trace,
  its lowering, the backend's compile or the cache's retrieval) of 5 ms or
  more, in order, with the same offsets (`small_events`: the rest, summed by
  name), and how often each plain event fired (cache hits, misses, requests);
* `root`: the whole of `GET /` before the server stops (the scorer's and the
  batcher's counters).

Run from the root of the checkout to be measured, as `serve_rings.py`:

    cd <checkout> && python3 <repo>/tools/chip_probes/setup_in_cell.py <out.json> \
        --workload trinity-large-l5.serve-steady --seed N --seconds 40 --trace 0

Nothing here touches the measured window: the wrappers sit on set-up calls,
and the listeners fire only when something compiles.  The result line is
still the last line of stdout.

`SETUP_IN_CELL_PROFILE=2048,16384` runs those rungs' `_compile` (`_lower`
since PR 49) under cProfile and keeps the 60 dearest functions of each (`profiles`, by own
time and cumulative), with the process's state at that point (`state`:
threads, stack depth, log levels, profile and trace hooks, GC counts);
`SETUP_IN_CELL_SAMPLE=1` instead samples the main thread's stack every 5 ms
from a second thread while the scorer is built, and keeps per `_compile` the
functions seen most often (`samples`: anywhere on the stack, and innermost):
a profile that does not slow what it watches.  `SETUP_IN_CELL_STOP=1` ends
the process once the deployment stands (no window, no audit, no result
line): a set-up alone, in a third of the time.
"""
import cProfile
import collections
import functools
import gc
import io
import json
import logging
import pstats
import threading
import traceback
import os
import sys
import time

T0 = time.perf_counter()
WALL0 = time.time()
BENCH = os.path.join(os.getcwd(), "benchmark")
sys.path[:0] = [BENCH, os.getcwd()]  # run.py, and the program

import importlib  # noqa: E402

import jax  # noqa: E402
from jax import monitoring  # noqa: E402

import run as bench_run  # noqa: E402

out = sys.argv.pop(1)
doc = {"phases": [], "events": [], "counts": collections.Counter()}
monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: doc["events"].append(
        {"name": name, "s": secs, "at": time.perf_counter() - T0}))
monitoring.register_event_listener(
    lambda name, **kw: doc["counts"].update([name]))


def timed(owner, attr, label=None):
    fn = getattr(owner, attr)

    @functools.wraps(fn)
    def wrapper(*a, **kw):
        t = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            name = label(*a, **kw) if callable(label) else (label or attr)
            doc["phases"].append({"name": name, "at": t - T0,
                                  "s": time.perf_counter() - t})
    setattr(owner, attr, wrapper)


def cache_dir():
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or mesh_mod.COMPILE_CACHE_DIR)


def store_dir():
    return os.path.normpath(cache_dir()) + "-programs"


def cache_entries(d=None):
    d = d or cache_dir()
    if not d or not os.path.isdir(d):
        return None
    rows = []
    for name in sorted(os.listdir(d)):
        st = os.stat(os.path.join(d, name))
        rows.append({"name": name, "bytes": st.st_size,
                     "written_by_this_run": st.st_mtime >= WALL0})
    return {"dir": d, "entries": len(rows),
            "bytes": sum(r["bytes"] for r in rows),
            "large": [r for r in rows if r["bytes"] >= 1 << 20]}


from predictionio_tpu.parallel import mesh as mesh_mod  # noqa: E402
from predictionio_tpu.serving import query_server, rungs, seqpath  # noqa: E402

doc["cache_before"] = cache_entries()
doc["store_before"] = cache_entries(store_dir())

Scorer = seqpath.PackedSequenceScorer
timed(Scorer, "__init__", "scorer.__init__")
if hasattr(rungs.RungPrograms, "_ready"):  # since PR 49, both scorers
    from predictionio_tpu.serving import program_store

    timed(rungs.RungPrograms, "_ready", lambda self, r, *a: f"compile.{r}")
    timed(program_store.ProgramStore, "load", "store.load")
    timed(program_store.ProgramStore, "save", "store.save")
    LOWER = "_lower"
else:
    timed(Scorer, "_compile", lambda self, t: f"compile.{t}")
    LOWER = "_compile"
timed(rungs, "measure_lag")
timed(rungs, "program_bytes")
timed(query_server.QueryServer, "__init__", "QueryServer.__init__")
timed(query_server.QueryServer, "start", "QueryServer.start")


def warm_by_rung(self, warm_args):
    """`RungPrograms._warm`, each rung's run timed."""
    for t in self.ladder:
        args = warm_args(t)
        t0 = time.perf_counter()
        jax.block_until_ready(self.fns[t](*args))
        doc["phases"].append({"name": f"warm.{t}", "at": t0 - T0,
                              "s": time.perf_counter() - t0})
        self.warmup_executions += 1


rungs.RungPrograms._warm = warm_by_rung
timed(rungs.RungPrograms, "_warm", "warm")

PROFILE = {int(t) for t in
           os.environ.get("SETUP_IN_CELL_PROFILE", "").split(",") if t}
if PROFILE:
    compile_rung = getattr(Scorer, LOWER)
    doc["profiles"] = {}

    def compile_under_profile(self, t):
        if t not in PROFILE:
            return compile_rung(self, t)
        doc["state"] = {
            "threads": [th.name for th in threading.enumerate()],
            "stack_depth": len(traceback.extract_stack()),
            "log_levels": {name: logging.getLogger(name).getEffectiveLevel()
                           for name in ("", "jax", "jax._src.dispatch")},
            "sys.getprofile": repr(sys.getprofile()),
            "sys.gettrace": repr(sys.gettrace()),
            "switchinterval": sys.getswitchinterval(),
            "gc": gc.get_stats(), "gc_objects": len(gc.get_objects()),
        }
        prof = cProfile.Profile()
        try:
            return prof.runcall(compile_rung, self, t)
        finally:
            doc["profiles"][str(t)] = {}
            for order in ("tottime", "cumulative"):
                text = io.StringIO()
                pstats.Stats(prof, stream=text).sort_stats(order).print_stats(60)
                doc["profiles"][str(t)][order] = text.getvalue()

    setattr(Scorer, LOWER, compile_under_profile)

cell = sys.argv[sys.argv.index("--workload") + 1]
config = next(w["config"] for w in
              bench_run.load_json(os.getcwd(), "BENCHMARK.json")["workloads"]
              if w["name"] == cell)
family = importlib.import_module("pio_bench.engines." + bench_run.load_json(
    BENCH, "configs", config + ".json")["engine"])
stop = family.Deployment.stop


def keep_then_stop(self):
    try:
        doc["root"] = self.root()
        doc["seconds"] = self.seconds
    except Exception as e:  # the probe never fails the run
        print(f"setup_in_cell: {e}", file=sys.stderr)
    stop(self)


if os.environ.get("SETUP_IN_CELL_SAMPLE"):
    main_id = threading.main_thread().ident
    sampling = {"on": False, "rows": []}  # (seconds from T0, stack)

    def sample():
        while True:
            time.sleep(0.005)
            if not sampling["on"]:
                continue
            frame, stack = sys._current_frames().get(main_id), []
            while frame is not None and len(stack) < 200:
                code = frame.f_code
                stack.append(f"{code.co_filename.rsplit('site-packages/', 1)[-1]}"
                             f":{code.co_name}")
                frame = frame.f_back
            sampling["rows"].append((time.perf_counter() - T0, stack))

    threading.Thread(target=sample, daemon=True, name="sampler").start()
    build = Scorer.__init__

    def build_sampled(self, *a, **kw):
        sampling["on"] = True
        try:
            return build(self, *a, **kw)
        finally:
            sampling["on"] = False
            doc["samples"] = {}
            for ph in doc["phases"]:
                if not ph["name"].startswith("compile."):
                    continue
                rows = [st for at, st in sampling["rows"]
                        if ph["at"] <= at <= ph["at"] + ph["s"]]
                on_stack = collections.Counter(
                    f for st in rows for f in set(st))
                inner = collections.Counter(st[0] for st in rows if st)
                doc["samples"][ph["name"]] = {
                    "n": len(rows), "on_stack": on_stack.most_common(70),
                    "innermost": inner.most_common(30)}

    Scorer.__init__ = build_sampled

family.Deployment.stop = keep_then_stop
if os.environ.get("SETUP_IN_CELL_STOP"):
    deploy = family.Deployment.__init__

    def deploy_then_end(self, *a, **kw):
        deploy(self, *a, **kw)
        doc["seconds"] = self.seconds
        try:
            doc["root"] = self.root()
        except Exception as e:  # the probe never fails the run
            print(f"setup_in_cell: {e}", file=sys.stderr)
        raise KeyboardInterrupt("SETUP_IN_CELL_STOP")

    family.Deployment.__init__ = deploy_then_end
rc = 1
try:
    rc = bench_run.main()
except KeyboardInterrupt:
    if not os.environ.get("SETUP_IN_CELL_STOP"):
        raise
    rc = 0
finally:
    # read after `MeshContext.create()` has placed the cache
    doc["env"] = {
        **{k: v for k, v in os.environ.items()
           if k.startswith(("JAX_", "XLA_", "LIBTPU", "TPU_"))},
        "config.cache_dir": jax.config.jax_compilation_cache_dir,
        "config.cache_max_size": jax.config.jax_compilation_cache_max_size,
        "config.min_compile_time_secs":
            jax.config.jax_persistent_cache_min_compile_time_secs,
        "config.min_entry_size_bytes":
            jax.config.jax_persistent_cache_min_entry_size_bytes,
    }
    doc["cache_after"] = cache_entries()
    doc["store_after"] = cache_entries(store_dir())
    doc["counts"] = dict(doc["counts"])
    # the thousands of sub-millisecond traces of inner functions: summed
    small = collections.defaultdict(lambda: [0, 0.0])
    for e in doc["events"]:
        if e["s"] < 0.005:
            small[e["name"]][0] += 1
            small[e["name"]][1] += e["s"]
    doc["events"] = [e for e in doc["events"] if e["s"] >= 0.005]
    doc["small_events"] = {k: {"n": n, "s": s} for k, (n, s) in small.items()}
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f)
if os.environ.get("SETUP_IN_CELL_STOP"):
    sys.stdout.flush()
    os._exit(rc)  # the server's threads are not ours to stop
sys.exit(rc)
