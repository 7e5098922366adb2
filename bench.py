"""Benchmark: ALS training throughput (events/sec/chip) on the local device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The reference publishes no numbers (BASELINE.md); vs_baseline is measured
against the driver-set north star: MovieLens-25M × 20 iterations on v5e-16
in 60 s ⇒ ~520,833 events/sec/chip.  vs_baseline = value / north_star.

Honesty contract: the JSON line always carries ``platform``,
``n_devices``, and the actual ``workload`` dims.  Without an accelerator
the bench exits non-zero; ``BENCH_PLATFORM=cpu`` is the one explicit way to
run the shrunken workload on the CPU, and such a run reports
``"fallback": true`` and ``"vs_baseline": null`` — a CPU number must never
be readable as progress against the TPU north star.

Workload distributions (VERDICT item 2): by default the bench runs the
uniform workload (primary metric) AND a Zipf-skewed workload whose item
popularity follows a power law like MovieLens-25M's catalog (hot ids
contiguous — the worst case for range-blocking).  ``BENCH_DIST`` narrows to
``uniform`` or ``zipf``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

NORTH_STAR_EVENTS_PER_SEC_PER_CHIP = 25_000_000 * 20 / (60 * 16)


def _sample_ids(rng, n: int, size: int, dist: str, s: float, q: float = 50.0) -> np.ndarray:
    """Entity ids from the named distribution.

    ``zipf``: Zipf-Mandelbrot P(id=k) ∝ (k+q)^-s over [0, n) with hot ids
    CONTIGUOUS at the low end — the adversarial layout for contiguous-range
    blocking.  The q shift matches real catalogs: at s=1.1, q=50 over 59k
    items the hottest item draws ~0.4% of ratings, like ML-25M's ~0.32%
    (a pure Zipf head would take ~10%, which no real catalog does).
    """
    from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights

    if dist == "uniform":
        return rng.integers(0, n, size).astype(np.int32)
    p = zipf_mandelbrot_weights(n, s=s, q=q)
    return rng.choice(n, size=size, p=p).astype(np.int32)


def _make_interactions(dist: str, n_users: int, n_items: int, n_ratings: int):
    from predictionio_tpu.data.batch import Interactions
    from predictionio_tpu.data.bimap import BiMap

    rng = np.random.default_rng(0)
    inter = Interactions(
        user=_sample_ids(rng, n_users, n_ratings, dist, s=0.7),
        item=_sample_ids(rng, n_items, n_ratings, dist, s=1.1),
        rating=rng.uniform(1.0, 5.0, n_ratings).astype(np.float32),
        t=np.zeros(n_ratings),
        user_map=None,
        item_map=None,
    )
    inter.user_map = BiMap({f"u{i}": i for i in range(n_users)})
    inter.item_map = BiMap({f"i{i}": i for i in range(n_items)})
    return inter


def _timed_run(ctx, inter, rank, iterations, dtype, n_chips, rebalance=True):
    from predictionio_tpu.models import als

    # warm-up: compile the step (first TPU compile is slow, cached after)
    als.train_als(
        ctx, inter, als.ALSConfig(rank=rank, iterations=1,
                                  compute_dtype=dtype, rebalance=rebalance)
    )
    t0 = time.perf_counter()
    model = als.train_als(
        ctx,
        inter,
        als.ALSConfig(rank=rank, iterations=iterations, compute_dtype=dtype,
                      rebalance=rebalance),
    )
    dt = time.perf_counter() - t0
    return len(inter.rating) * iterations / dt / n_chips, model, dt


# The per-chip peak table and the analytic ALS cost model live in
# obs/devprof (shared with the live serving/train utilization accountants
# — one formula, one denominator, everywhere).  The table is keyed by
# device_kind; a device that is not in it (any CPU) reports null mfu.
from predictionio_tpu.obs.devprof import PEAKS as _PEAKS  # noqa: E402
from predictionio_tpu.obs.devprof import (  # noqa: E402
    train_utilization as _utilization,
)


def _device_busy_seconds(trace_dir: str) -> tuple:
    """Sum device-plane busy time from a jax.profiler xplane trace.

    Per plane, lines hold nested op events (durations overlap across
    levels); the max single-line sum is that device's busy wall — summed
    over ``/device:`` planes. Returns ``(busy_s, n_planes)`` or
    ``(None, 0)`` when the trace has no device plane (CPU runs: the host
    plane interleaves thread-pool events and would sum past the wall).
    """
    import glob

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    files = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    space = xplane_pb2.XSpace()
    with open(max(files, key=os.path.getmtime), "rb") as f:
        space.ParseFromString(f.read())

    def busy(plane):
        sums = [
            sum(ev.duration_ps for ev in line.events) / 1e12
            for line in plane.lines
        ]
        return max(sums) if sums else 0.0

    device = [p for p in space.planes if p.name.startswith("/device:")]
    if not device:
        return None, 0
    return sum(busy(p) for p in device), len(device)


def _measured_utilization(ctx, inter, rank, dtype, device_kind,
                          rebalance=True) -> dict:
    """MEASURED companions to the analytic cost model (VERDICT r4 weak 2):

    * ``measured_device_time_fraction`` — profiler-traced device busy time
      over the traced wall for a 2-iteration train (a wrong analytic
      model can't hide a regression here);
    * ``xla_*`` — the compiler's own flops/bytes for the actual optimized
      per-device HLO (``dense_step_cost_analysis``), with achieved rates
      + utilization against the same peaks as the analytic fields.
    """
    import tempfile

    import jax

    from predictionio_tpu.models import als

    out = {}
    # solver pinned to dense: the measured fields model the flagship path
    # regardless of a PIO_ALS_SOLVER A/B override in the environment;
    # rebalance follows the benched cell so the trace describes the SAME
    # layout the record's workload claims
    cfg = als.ALSConfig(
        rank=rank, iterations=2, compute_dtype=dtype, solver="dense",
        rebalance=rebalance,
    )
    als.train_als(ctx, inter, als.ALSConfig(
        rank=rank, iterations=1, compute_dtype=dtype, solver="dense",
        rebalance=rebalance,
    ))  # compile outside the trace
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            # timed INSIDE the trace block: profiler stop + xplane
            # serialization must not deflate the measured rates
            t0 = time.perf_counter()
            als.train_als(ctx, inter, cfg)
            wall = time.perf_counter() - t0
        busy, n_planes = _device_busy_seconds(td)
        out["measured_device_time_fraction"] = (
            round(busy / (wall * n_planes), 4) if n_planes else None
        )
        out["traced_wall_sec"] = round(wall, 3)
    ca = als.dense_step_cost_analysis(ctx, inter, als.ALSConfig(
        rank=rank, iterations=1, compute_dtype=dtype, solver="dense",
        rebalance=rebalance,
    ))
    flops, nbytes = (
        ca["flops_per_iter_per_device"], ca["bytes_per_iter_per_device"]
    )
    if flops and nbytes:
        # Rate basis: the profiler's DEVICE BUSY time when the trace has
        # device planes — dividing compiled per-iteration device cost by
        # whole-call wall time (host blocking prep, dispatch, readback)
        # understates what the chip actually sustained while running.
        # CPU runs have no device plane; they fall back to wall and say so.
        if busy and n_planes:
            per_dev = busy / n_planes
            out["xla_rate_basis"] = "device_busy"
        else:
            per_dev = wall  # SPMD: all devices run the whole step
            out["xla_rate_basis"] = "wall"
        out["xla_flops_per_sec_per_chip"] = round(
            flops * cfg.iterations / per_dev / 1e9, 2
        )  # GFLOP/s
        out["xla_hbm_gbps_per_chip"] = round(
            nbytes * cfg.iterations / per_dev / 1e9, 2
        )
        peak = _PEAKS.get(device_kind)
        if peak:
            out["xla_mfu"] = round(
                flops * cfg.iterations / per_dev / peak["flops"], 6
            )
            out["xla_hbm_util"] = round(
                nbytes * cfg.iterations / per_dev / peak["hbm_gbps"], 6
            )
    return out


def _scorer_latency(ctx, model, on_device, n_queries=300, warmup=20) -> dict:
    """p50/p99 of direct ALSScorer.recommend (the in-process serving path)."""
    from predictionio_tpu.models.als import ALSScorer

    scorer = ALSScorer(ctx, model, on_device=on_device)
    rng = np.random.default_rng(7)
    users = rng.integers(0, model.user_factors.shape[0], n_queries + warmup)
    lat = []
    for i, u in enumerate(users):
        t0 = time.perf_counter()
        scorer.recommend(int(u), 10)
        if i >= warmup:
            lat.append(time.perf_counter() - t0)
    lat.sort()
    q = lambda p: round(lat[min(int(p * len(lat)), len(lat) - 1)] * 1e3, 3)
    return {
        "p50": q(0.50), "p99": q(0.99), "queries": n_queries,
        "on_device": scorer.on_device,
    }


def _zipf_serving_phase(engine, storage, ctx, users) -> dict:
    """The Zipf-gap record: same trained model, a SECOND QueryServer with
    the skew path on (result cache + single-flight + hot-set), driven with
    uniform-rotation traffic and then Zipf-Mandelbrot traffic over the
    same key set.

    The cache is sized WELL UNDER the key population (1024 entries vs
    ~4000 keys), so uniform rotation thrashes the LRU and earns ~nothing
    — the ratio isolates what the stack extracts from SKEW, not from
    caching per se.  ``ratio_vs_uniform`` is zipf QPS over uniform QPS;
    the gate (tools/bench_matrix.py) is >= 1.0, i.e. skewed traffic must
    be at least as fast as uniform instead of 0.57x (the pre-cache seed
    measurement).  Hit/coalesce rates come from the server's own stats
    deltas per phase, and the record carries proof the ``pio_result_cache_*``
    families were live at ``/metrics`` while the ratio was measured.
    """
    import urllib.request as _rq

    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.serving.result_cache import ResultCache
    from predictionio_tpu.tools.loadtest import run_loadtest, scrape_metrics

    n_keys = int(os.environ.get("BENCH_ZIPF_KEYS", 4000))
    requests = int(os.environ.get("BENCH_ZIPF_REQUESTS", 400))
    cache = ResultCache(
        max_entries=int(os.environ.get("BENCH_ZIPF_CACHE_MAX", 1024))
    )
    hot_env = {
        "PIO_HOTSET_SIZE": os.environ.get("BENCH_ZIPF_HOTSET", "256"),
        # re-rank often enough that a bench-sized run materializes a table
        "PIO_HOTSET_REFRESH_QUERIES": os.environ.get(
            "BENCH_ZIPF_HOTSET_REFRESH", "128"
        ),
    }
    prev = {k: os.environ.get(k) for k in hot_env}
    os.environ.update(hot_env)
    try:
        qs = QueryServer(
            engine, storage=storage, ctx=ctx, batching=True,
            result_cache=cache, coalesce=True,
        )
        port = qs.start("127.0.0.1", 0)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    try:
        url = f"http://127.0.0.1:{port}"

        def stats() -> dict:
            with _rq.urlopen(url + "/", timeout=10) as r:
                return json.loads(r.read().decode())

        keys = [f"u{u}" for u in dict.fromkeys(users.tolist())][:n_keys]
        sample = {"user": keys}
        run_loadtest(url, {"num": 10}, requests=40, concurrency=2,
                     samples={"user": keys[:64]})  # warm jit + hot-set
        # each phase starts with a COLD result cache: hits below are earned
        # by repeats within the phase's own draw, i.e. by its skew alone
        cache.clear()
        s0 = stats()
        uni = run_loadtest(url, {"num": 10}, requests=requests,
                           concurrency=4, samples=sample)
        s1 = stats()
        cache.clear()
        zipf = run_loadtest(url, {"num": 10}, requests=requests,
                            concurrency=4, samples=sample, dist="zipf")
        s2 = stats()
        series = scrape_metrics(url)
        metrics_live = any(
            n == "pio_result_cache_lookups_total" for (n, _) in series
        )
        # which scan the cache-MISS path takes: pio_ivf_* families emit
        # only while an IVF index is live, so presence IS the backend
        ivf_live = any(n == "pio_ivf_info" for (n, _) in series)
        scanned = [
            v for (n, _), v in series.items()
            if n == "pio_ivf_scanned_fraction"
        ]
    finally:
        qs.stop()

    def phase_rates(a: dict, b: dict) -> dict:
        ca, cb = a.get("resultCache") or {}, b.get("resultCache") or {}
        ba, bb = a.get("batching") or {}, b.get("batching") or {}
        lookups = (cb.get("hits", 0) - ca.get("hits", 0)) + (
            cb.get("misses", 0) - ca.get("misses", 0)
        )
        hits = cb.get("hits", 0) - ca.get("hits", 0)
        queries = bb.get("queries", 0) - ba.get("queries", 0)
        coalesced = bb.get("coalesced", 0) - ba.get("coalesced", 0)
        return {
            "hit_rate": round(hits / lookups, 4) if lookups else None,
            "coalesce_rate": (
                round(coalesced / queries, 4) if queries else None
            ),
        }

    out = {
        "keys": len(keys),
        "cache_max": cache.max_entries,
        "uniform": {"qps": uni["qps"], "p50": uni["p50Ms"],
                    "p99": uni["p99Ms"], **phase_rates(s0, s1)},
        "zipf": {"qps": zipf["qps"], "p50": zipf["p50Ms"],
                 "p99": zipf["p99Ms"], **phase_rates(s1, s2)},
        "ratio_vs_uniform": (
            round(zipf["qps"] / uni["qps"], 4) if uni["qps"] else None
        ),
        "errors": uni["errors"] + zipf["errors"],
        "metrics_live": metrics_live,
        "retrieval_backend": "ivf" if ivf_live else "exact",
    }
    if scanned:
        out["ivf_scanned_fraction"] = max(scanned)
    hot = ((s2.get("fastpath") or [{}])[0] or {}).get("hotset")
    if hot:
        out["hotset"] = {
            "resident": hot.get("resident"), "hit_rate": hot.get("hit_rate"),
        }
    if zipf.get("perKey"):
        hotkeys = zipf["perKey"].get("hotKeys") or []
        cold = zipf["perKey"].get("coldTail") or {}
        out["zipf"]["hot_key_p50"] = (
            hotkeys[0]["p50Ms"] if hotkeys else None
        )
        out["zipf"]["cold_tail_p50"] = cold.get("p50Ms")
    return out


def _http_latency(ctx, dist, n_users, n_items) -> dict:
    """p50/p99 of the FULL REST predict path: synthetic events → real
    template train → QueryServer → loadtest POST /queries.json.

    Parity: the reference's per-request serving timer
    (core/.../workflow/CreateServer.scala:597-604). The model's factor
    SHAPES match the training bench (scoring cost is O(n_items·k) per
    query, independent of how many ratings trained it), so a small
    training pass serves an honestly-sized catalog.
    """
    import uuid

    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.batch import EventBatch
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.templates.recommendation import RecommendationEngine
    from predictionio_tpu.tools.loadtest import run_loadtest

    n_events = int(os.environ.get("BENCH_SERVING_EVENTS", 1_000_000))
    src = "BENCH" + uuid.uuid4().hex[:6].upper()
    storage = Storage(env={
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    })
    store_mod.set_storage(storage)
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "benchapp"))
        storage.get_l_events().init(app_id)
        rng = np.random.default_rng(11)
        users = _sample_ids(rng, n_users, n_events, dist, s=0.7)
        items = _sample_ids(rng, n_items, n_events, dist, s=1.1)
        now = time.time()
        batch = EventBatch(
            event=np.full(n_events, "rate", object),
            entity_type=np.full(n_events, "user", object),
            entity_id=np.array([f"u{u}" for u in users], object),
            target_entity_type=np.full(n_events, "item", object),
            target_entity_id=np.array([f"i{i}" for i in items], object),
            event_time=np.full(n_events, now, np.float64),
            properties=[
                {"rating": float(r)}
                for r in rng.integers(1, 6, n_events)
            ],
        )
        storage.get_p_events().write(batch, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "benchapp"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 10, "numIterations": 2}}
            ],
        })
        run_train(engine, ep, "bench", storage=storage, ctx=ctx)
        # batching=True is the serving fast path under bench: AOT-warmed
        # bucketed compile cache + adaptive micro-batching (ISSUE r06)
        qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
        port = qs.start("127.0.0.1", 0)
        try:
            url = f"http://127.0.0.1:{port}"

            def server_stats() -> dict:
                import urllib.request as _rq

                with _rq.urlopen(url + "/", timeout=10) as r:
                    return json.loads(r.read().decode())

            # ≥100 DISTINCT users rotated per request: one fixed payload
            # would measure one warm jit path + one hot cache line and
            # flatter the tail (VERDICT r4)
            distinct = [
                f"u{u}" for u in dict.fromkeys(users.tolist())
            ][:256]
            sample = {"user": distinct}
            run_loadtest(url, {"num": 10}, requests=40,
                         concurrency=2, samples=sample)  # warm path + jit
            before = server_stats()
            res = run_loadtest(
                url, {"num": 10},
                requests=int(os.environ.get("BENCH_HTTP_REQUESTS", 300)),
                concurrency=4, samples=sample,
            )
            after = server_stats()
        finally:
            qs.stop()

        def compiles(stats: dict) -> int:
            return sum(
                fp.get("compile_count", 0) for fp in stats.get("fastpath") or []
            )

        out = {
            "p50": res["p50Ms"], "p99": res["p99Ms"], "qps": res["qps"],
            "requests": res["requests"], "errors": res["errors"],
            "serving_events": n_events, "distinct_users": len(distinct),
            # acceptance: zero compiles DURING traffic — the bucket ladder
            # was fully AOT-warmed at deploy, so this must be 0
            "recompiles": compiles(after) - compiles(before),
        }
        batching = after.get("batching")
        if batching:
            out["batch_avg"] = batching.get("avg_batch")
            out["batches"] = batching.get("batches")
        fp_after = after.get("fastpath") or []
        if fp_after:
            out["fastpath_calls"] = sum(f.get("calls", 0) for f in fp_after)
            occ = [
                f["row_occupancy"]
                for f in fp_after
                if f.get("row_occupancy") is not None
            ]
            out["batch_occupancy"] = occ[0] if len(occ) == 1 else (occ or None)
        # live serving utilization (ISSUE 8): the scorer's cost-annotated
        # dispatch accountant, read through the same stats surface the
        # /metrics bridge uses — bench_matrix gates these being non-null
        dev = next(
            (f.get("devprof") for f in fp_after if f.get("devprof")), None
        ) or {}
        out["serving_utilization"] = {
            "busy_fraction": dev.get("busy_fraction"),
            "flops_per_s": dev.get("flops_per_s"),
            "hbm_gbps": dev.get("hbm_gbps"),
            "mfu": dev.get("mfu"),
            "hbm_util": dev.get("hbm_util"),
            "dispatches": dev.get("dispatches_total"),
        }
        # resilience layer under a NON-chaos run: every counter must be
        # quiet — any shed/deadline/degraded/error here is a regression
        res_stats = after.get("resilience") or {}
        counters = res_stats.get("counters") or {}
        out["resilience"] = {
            "shed": counters.get("shed", 0) + res.get("shed", 0),
            "deadline_exceeded": counters.get("deadline_exceeded", 0)
            + res.get("deadlineExceeded", 0),
            "breaker_open": counters.get("breaker_open", 0),
            "degraded": counters.get("degraded", 0),
            "query_errors": counters.get("query_errors", 0),
            "clean": res["errors"] == 0
            and counters.get("shed", 0) == 0
            and counters.get("deadline_exceeded", 0) == 0
            and counters.get("degraded", 0) == 0,
        }
        if os.environ.get("BENCH_ZIPF", "1") != "0":
            # the zipf-gap phase must never kill the http record it rides on
            try:
                out["zipf"] = _zipf_serving_phase(engine, storage, ctx, users)
            except Exception as e:
                print(f"WARNING: zipf serving phase failed: {e}",
                      file=sys.stderr)
                out["zipf"] = {"error": str(e)}
            print(f"INFO: zipf serving: {out['zipf']}", file=sys.stderr)
        return out
    finally:
        store_mod.set_storage(None)
        from predictionio_tpu.data.storage import memory

        memory.reset_store(src)


def _observability_bench(ctx) -> dict:
    """Telemetry overhead gate: HTTP serving p50 with the obs subsystem ON
    (trace sampling forced to 1.0 — every request traced, the worst case)
    vs OFF (``telemetry=False``: no registry, no tracer, the pre-obs hot
    loop), same trained model, same rotated payloads.

    ``overhead_ratio`` is p50_on / p50_off; the gate is <3%.  Each config
    takes the min-of-3 p50 so one GC pause or scheduler hiccup can't fail
    the gate on noise.  The ON server is also asked for ``/metrics`` and
    ``/trace/recent.json`` so the record carries proof the exposition was
    live while the gate was measured.
    """
    import urllib.request as _rq
    import uuid

    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.batch import EventBatch
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.templates.recommendation import RecommendationEngine
    from predictionio_tpu.tools.loadtest import run_loadtest

    n_events = int(os.environ.get("BENCH_OBS_EVENTS", 100_000))
    n_users, n_items = 5000, 2000
    requests = int(os.environ.get("BENCH_OBS_REQUESTS", 300))
    src = "OBSBENCH" + uuid.uuid4().hex[:6].upper()
    storage = Storage(env={
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    })
    store_mod.set_storage(storage)
    prev_sample = os.environ.get("PIO_TRACE_SAMPLE")
    try:
        app_id = storage.get_meta_data_apps().insert(App(0, "obsbenchapp"))
        storage.get_l_events().init(app_id)
        rng = np.random.default_rng(23)
        users = rng.integers(0, n_users, n_events)
        items = rng.integers(0, n_items, n_events)
        now = time.time()
        batch = EventBatch(
            event=np.full(n_events, "rate", object),
            entity_type=np.full(n_events, "user", object),
            entity_id=np.array([f"u{u}" for u in users], object),
            target_entity_type=np.full(n_events, "item", object),
            target_entity_id=np.array([f"i{i}" for i in items], object),
            event_time=np.full(n_events, now, np.float64),
            properties=[
                {"rating": float(r)} for r in rng.integers(1, 6, n_events)
            ],
        )
        storage.get_p_events().write(batch, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "obsbenchapp"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 10, "numIterations": 2}}
            ],
        })
        run_train(engine, ep, "obsbench", storage=storage, ctx=ctx)
        distinct = [f"u{u}" for u in dict.fromkeys(users.tolist())][:256]
        sample = {"user": distinct}
        os.environ["PIO_TRACE_SAMPLE"] = "1.0"  # every request traced

        def measure(telemetry: bool) -> tuple:
            qs = QueryServer(
                engine, storage=storage, ctx=ctx, batching=True,
                telemetry=telemetry,
            )
            port = qs.start("127.0.0.1", 0)
            url = f"http://127.0.0.1:{port}"
            try:
                run_loadtest(url, {"num": 10}, requests=60, concurrency=2,
                             samples=sample)  # warm the path + jit
                p50s = []
                for _ in range(3):
                    r = run_loadtest(url, {"num": 10}, requests=requests,
                                     concurrency=4, samples=sample)
                    p50s.append(r["p50Ms"])
                proof = None
                if telemetry:
                    with _rq.urlopen(url + "/metrics", timeout=10) as r:
                        text = r.read().decode()
                    from predictionio_tpu.obs.metrics import parse_prometheus

                    series = parse_prometheus(text)
                    with _rq.urlopen(
                        url + "/trace/recent.json?limit=50", timeout=10
                    ) as r:
                        traces = json.loads(r.read().decode())["traces"]
                    # newest trace is the /metrics scrape itself; the proof
                    # wants a QUERY trace with the full stage breakdown
                    qtraces = [
                        t for t in traces
                        if "/queries.json" in t.get("name", "")
                    ]
                    proof = {
                        "metric_series": len(series),
                        "trace_stages": sorted(
                            qtraces[0]["stagesMs"]
                        ) if qtraces else [],
                    }
                return min(p50s), proof
            finally:
                qs.stop()

        p50_on, proof = measure(True)
        p50_off, _ = measure(False)
        ratio = p50_on / p50_off if p50_off > 0 else float("nan")
        return {
            "p50_on_ms": p50_on,
            "p50_off_ms": p50_off,
            "overhead_ratio": round(ratio, 4),
            "gate": 1.03,
            "gate_pass": bool(ratio <= 1.03),
            "trace_sample": 1.0,
            "requests_per_run": requests,
            **(proof or {}),
        }
    finally:
        if prev_sample is None:
            os.environ.pop("PIO_TRACE_SAMPLE", None)
        else:
            os.environ["PIO_TRACE_SAMPLE"] = prev_sample
        store_mod.set_storage(None)
        from predictionio_tpu.data.storage import memory

        memory.reset_store(src)


def _ingest_bench() -> dict:
    """Ingest fast-path evidence on the sqlite backend (the fsync-bound
    one): per-event-commit baseline vs one-transaction ``insert_batch`` vs
    the write-behind buffer, all single node, file-backed.

    The headline ``vs_baseline`` is batched/baseline events/s —
    acceptance wants ≥10x.  The buffer row adds concurrent durable-ack
    latency (client-observed p50/p99) and the flush batch-size histogram,
    the group-commit's signature.
    """
    import shutil
    import tempfile
    import threading

    from predictionio_tpu.data.api.ingest_buffer import IngestBuffer
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db

    n = int(os.environ.get("BENCH_INGEST_EVENTS", 3000))
    # the per-event-commit baseline is ~20-50x slower; cap its share of
    # wall time without losing measurement stability
    n_base = int(os.environ.get("BENCH_INGEST_BASELINE_EVENTS", min(n, 1000)))
    batch_size = int(os.environ.get("BENCH_INGEST_BATCH", 50))
    tmp = tempfile.mkdtemp(prefix="pio-ingest-bench-")
    src = "INGESTBENCH"
    path = os.path.join(tmp, "events.sqlite")
    base_path = os.path.join(tmp, "events_baseline.sqlite")
    storage = Storage(env={
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": path,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    })
    try:
        le = storage.get_l_events()
        le.init(1)

        def make_events(tag, count):
            return [
                Event(
                    event="rate", entity_type="user",
                    entity_id=f"{tag}u{i}", target_entity_type="item",
                    target_entity_id=f"i{i % 97}",
                    properties={"rating": float(i % 5 + 1)},
                )
                for i in range(count)
            ]

        # baseline: the pre-batching ingest path — one DAO insert (one
        # commit) per event, single thread, under the seed's sqlite
        # config (rollback journal, synchronous=FULL).  The PR moved the
        # events writer to WAL + synchronous=NORMAL, so the baseline runs
        # on its own file with the writer pragmas reset to the old values;
        # otherwise the comparison would hide the durability-config win.
        base_storage = Storage(env={
            f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
            f"PIO_STORAGE_SOURCES_{src}_PATH": base_path,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
        })
        from predictionio_tpu.data.storage.sqlite import (
            _INSERT_EVENT_SQL, _event_row, new_event_id,
        )

        base_le = base_storage.get_l_events()
        base_le.init(1)
        bconn, block = base_le.conn, base_le.lock  # the shared DAO conn
        bconn.execute("PRAGMA synchronous=FULL")
        evs = make_events("base", n_base)
        t0 = time.perf_counter()
        for e in evs:
            row = _event_row(e, e.event_id or new_event_id(), 1, None)
            with block:
                bconn.execute(_INSERT_EVENT_SQL, row)
                bconn.commit()
        base_dt = time.perf_counter() - t0
        baseline = n_base / base_dt

        # batched: insert_batch in endpoint-sized chunks, single thread
        evs = make_events("batch", n)
        t0 = time.perf_counter()
        for s in range(0, n, batch_size):
            le.insert_batch(evs[s:s + batch_size], 1)
        batch_dt = time.perf_counter() - t0
        batched = n / batch_dt

        # write-behind: concurrent producers, durable ack (wait for the
        # group commit); per-event ack latency is the client-visible cost
        buf = IngestBuffer(le, flush_ms=2.0, durable_ack=True)
        evs = make_events("buf", n)
        # each durable-ack producer has one event in flight, so the flush
        # coalesces ~`workers` events per commit — concurrency IS the
        # group-commit batch size
        workers = int(os.environ.get("BENCH_INGEST_WORKERS", 32))
        acks: list[float] = []
        ack_lock = threading.Lock()

        def producer(w):
            local = []
            for e in evs[w::workers]:
                t0 = time.perf_counter()
                if not buf.submit(e, 1).wait(30.0):
                    raise RuntimeError("ingest buffer ack timed out")
                local.append(time.perf_counter() - t0)
            with ack_lock:
                acks.extend(local)

        threads = [
            threading.Thread(target=producer, args=(w,)) for w in range(workers)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        buf_dt = time.perf_counter() - t0
        buf_stats = buf.stats()
        buf.close()
        acks.sort()
        q = lambda p: round(
            acks[min(int(p * len(acks)), len(acks) - 1)] * 1e3, 3
        )
        return {
            "backend": "sqlite",
            "events": n,
            "batch_size": batch_size,
            "baseline_events": n_base,
            "baseline_config": "per-event commit, rollback journal, synchronous=FULL",
            "baseline_events_per_sec": round(baseline, 1),
            "batched_events_per_sec": round(batched, 1),
            # the acceptance ratio: batched DAO path vs per-event commits
            "vs_baseline": round(batched / baseline, 2),
            "buffered_events_per_sec": round(n / buf_dt, 1),
            "buffered_vs_baseline": round(n / buf_dt / baseline, 2),
            "ack_p50_ms": q(0.50),
            "ack_p99_ms": q(0.99),
            "flushes": buf_stats["flushes"],
            "avg_flush_batch": buf_stats["avg_flush_batch"],
            "flush_batch_hist": buf_stats["flush_batch_hist"],
            "flush_errors": buf_stats["flush_errors"],
        }
    finally:
        try:
            close_db(path)
            close_db(base_path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def _durability_bench() -> dict:
    """Durability cost evidence: fast-ack throughput with the ingest WAL
    at each fsync policy (off / group / always), plus replay speed.

    The acceptance gate is ``group_vs_off`` — the group-commit fsync
    policy must hold within 2x of no-fsync, which is the whole point of
    amortizing the fsync across the group window.  Replay is timed
    separately (journal ~10k events, then replay + batch-insert into a
    cold store) and normalized to seconds per 10k events.
    """
    import shutil
    import tempfile

    from predictionio_tpu.data.api.ingest_buffer import (
        IngestBuffer, wal_decode, wal_encode,
    )
    from predictionio_tpu.data.api.wal import WriteAheadLog
    from predictionio_tpu.data.event import Event
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db

    n = int(os.environ.get("BENCH_DURABILITY_EVENTS", 3000))
    n_replay = int(os.environ.get("BENCH_DURABILITY_REPLAY_EVENTS", 10000))

    def make_events(tag, count):
        return [
            Event(
                event="rate", entity_type="user",
                entity_id=f"{tag}u{i}", target_entity_type="item",
                target_entity_id=f"i{i % 97}",
                properties={"rating": float(i % 5 + 1)},
            )
            for i in range(count)
        ]

    throughput: dict[str, float] = {}
    for policy in ("off", "group", "always"):
        tmp = tempfile.mkdtemp(prefix=f"pio-dur-bench-{policy}-")
        src = "DURBENCH"
        path = os.path.join(tmp, "events.sqlite")
        storage = Storage(env={
            f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
            f"PIO_STORAGE_SOURCES_{src}_PATH": path,
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
        })
        try:
            le = storage.get_l_events()
            le.init(1)
            wal = WriteAheadLog(os.path.join(tmp, "wal"), fsync=policy)
            # fast-ack: the WAL append inside submit() is the ack's
            # durability cost, so the submit loop's wall time IS the
            # client-visible fast-ack throughput under that policy
            buf = IngestBuffer(le, flush_ms=2.0, durable_ack=False, wal=wal)
            evs = make_events(policy, n)
            tickets = []
            t0 = time.perf_counter()
            for e in evs:
                tickets.append(buf.submit(e, 1))
            dt = time.perf_counter() - t0
            throughput[policy] = n / dt
            for t in tickets:
                t.wait(30.0)
            buf.close()
            wal.close()
        finally:
            try:
                close_db(path)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)

    # replay: journal n_replay events, then cold-start replay them into a
    # fresh store the way the event server does on restart
    tmp = tempfile.mkdtemp(prefix="pio-dur-bench-replay-")
    src = "DURBENCH"
    path = os.path.join(tmp, "events.sqlite")
    storage = Storage(env={
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": path,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    })
    try:
        wal = WriteAheadLog(os.path.join(tmp, "wal"), fsync="off")
        for e in make_events("replay", n_replay):
            wal.append(wal_encode(e, 1, None))
        wal.close()

        le = storage.get_l_events()
        le.init(1)
        wal2 = WriteAheadLog(os.path.join(tmp, "wal"), fsync="off")
        t0 = time.perf_counter()
        records = wal2.replay()
        events = [wal_decode(p)[0] for p in records]
        le.insert_batch(events, 1)
        wal2.reclaim_replayed()
        replay_dt = time.perf_counter() - t0
        wal2.close()
        replayed = len(records)
    finally:
        try:
            close_db(path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    return {
        "backend": "sqlite",
        "events": n,
        "fast_ack_events_per_sec": {
            k: round(v, 1) for k, v in throughput.items()
        },
        # acceptance: group-commit fsync within 2x of no fsync
        "group_vs_off": round(throughput["off"] / throughput["group"], 2),
        "always_vs_off": round(throughput["off"] / throughput["always"], 2),
        "replay_events": replayed,
        "replay_sec_per_10k": round(replay_dt * 10000.0 / max(replayed, 1), 3),
    }


def _kernel_bench(platform: str, n_items: int, rank: int) -> dict:
    """Score-kernel block: fused Pallas vs XLA reference, per factor dtype.

    Two kinds of evidence per dtype (f32/bf16/int8):

    * **Analytic roofline** at the artifact's serving shape — arithmetic
      intensity (FLOPs/byte) of one top-bucket dispatch for both kernels
      and the TPU-roofline MFU each can attain (min(peak, intensity·bw)
      / peak).  The fused kernel never round-trips the (B, I) score
      matrix through HBM, so its intensity gain over the reference is
      the headline number and the matrix gate (fused ≥ reference).
    * **Measured scores/s**, TPU only — on CPU the fused path runs the
      Pallas *interpreter*, so timing it would bench the interpreter,
      not the kernel; CPU artifacts carry ``measured: null``.

    Resident factor bytes per dtype come from actually quantizing a
    factor pair at the bench shape (scales included), so the int8 ≤ ½
    acceptance line is measured, not asserted.
    """
    import jax

    from predictionio_tpu.obs.devprof import (
        PEAKS, fused_score_cost, score_cost,
    )
    from predictionio_tpu.ops.quantize import quantize_factors
    from predictionio_tpu.ops.topk import gather_score_topk

    batch = int(os.environ.get("BENCH_KERNEL_BATCH", 256))
    top_k = int(os.environ.get("BENCH_KERNEL_TOPK", 100))
    peak = PEAKS["TPU v5 lite"]  # roofline projection is against v5e

    rng = np.random.default_rng(11)
    n_users = max(batch * 4, 1024)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)

    def roofline(flops: float, nbytes: float) -> dict:
        intensity = flops / nbytes
        attainable = min(peak["flops"], intensity * peak["hbm_gbps"])
        return {
            "intensity_flops_per_byte": round(intensity, 3),
            "roofline_mfu": round(attainable / peak["flops"], 4),
        }

    on_tpu = platform == "tpu"
    out: dict = {
        "shape": {
            "batch": batch, "items": n_items, "rank": rank, "top_k": top_k,
        },
        "measured_backend": platform if on_tpu else None,
        "dtypes": {},
    }
    f32_bytes = None
    for dtype in ("f32", "bf16", "int8"):
        Uq, us = quantize_factors(U, dtype)
        Vq, vs = quantize_factors(V, dtype)
        resident = sum(
            int(a.nbytes) for a in (Uq, Vq, us, vs) if a is not None
        )
        if dtype == "f32":
            f32_bytes = resident
        ref = roofline(*score_cost(batch, n_items, rank, dtype=dtype))
        fused = roofline(
            *fused_score_cost(batch, n_items, rank, top_k, dtype=dtype)
        )
        cell = {
            "reference": ref,
            "fused": fused,
            "intensity_gain": round(
                fused["intensity_flops_per_byte"]
                / ref["intensity_flops_per_byte"], 2
            ),
            "resident_factor_bytes": resident,
            "resident_vs_f32": round(resident / f32_bytes, 4),
        }
        if on_tpu:
            # measured A/B: same inputs, both backends, scores/s
            u_idx = rng.integers(0, n_users, batch).astype(np.int32)
            measured = {}
            for backend in ("reference", "fused"):
                fn = jax.jit(
                    lambda U_, V_, us_, vs_, idx_, _b=backend:
                    gather_score_topk(
                        U_, V_, idx_, top_k, u_scale=us_, v_scale=vs_,
                        backend=_b,
                    )
                )
                r = fn(Uq, Vq, us, vs, u_idx)
                jax.block_until_ready(r)  # compile + warm
                iters = int(os.environ.get("BENCH_KERNEL_ITERS", 30))
                t0 = time.perf_counter()
                for _ in range(iters):
                    r = fn(Uq, Vq, us, vs, u_idx)
                jax.block_until_ready(r)
                dt = time.perf_counter() - t0
                measured[backend] = round(batch * n_items * iters / dt, 1)
            cell["measured_scores_per_sec"] = measured
            cell["measured_gain"] = round(
                measured["fused"] / measured["reference"], 2
            )
        out["dtypes"][dtype] = cell

    f32 = out["dtypes"]["f32"]
    int8 = out["dtypes"]["int8"]
    # matrix gates: the fused kernel must not be below the reference on
    # the analytic model (and on silicon when measured), and int8 must at
    # least halve the resident factor footprint
    out["intensity_gain_f32"] = f32["intensity_gain"]
    out["int8_resident_vs_f32"] = int8["resident_vs_f32"]
    gate = f32["intensity_gain"] >= 1.0 and int8["resident_vs_f32"] <= 0.5
    if on_tpu:
        gate = gate and f32.get("measured_gain", 0.0) >= 1.0
    out["gate_pass"] = bool(gate)
    return out


def _train_kernel_bench(
    ctx, platform: str, n_users: int, n_items: int, n_ratings: int,
    rank: int,
) -> dict:
    """Training-kernel block: fused Pallas vs XLA reference, per COMPUTE
    dtype (``PIO_ALS_COMPUTE_DTYPE``).

    Two kinds of evidence per dtype (f32/bf16/int8):

    * **Analytic roofline** at the artifact's training shape — the
      reference backend priced with the gather term XLA actually pays
      (~512 B sector per factor row, ``als_train_cost_amplified``)
      against the fused kernel's one-sequential-V-read model
      (``fused_train_cost``), plus the expected ms/iteration each
      implies (max of compute time and HBM time at TPU peaks).  The
      matrix gate holds fused intensity STRICTLY above the reference
      for every dtype and the int8 one-pass V read to ≤ ½ the f32
      bytes.
    * **Equivalence** — a small train on the live mesh, fused (the real
      kernel body, interpret off-TPU) vs reference, per dtype; the f32
      factors must be BIT-equal, bf16/int8 within documented tolerance.

    Nothing here is measured.  On a TPU the block reports why instead of
    running: an explicit ``fused`` raises wherever the static dispatch
    rule refuses the kernel (``ops/train_kernel.refusal`` — bf16/int8
    always, f32 over the padded-tile VMEM budget, which this shape is),
    so there is no A/B to time until the kernel's layout is redesigned
    (ROADMAP A2, PERF.md §7).
    """
    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.obs.devprof import (
        PEAKS,
        als_train_cost_amplified,
        fused_train_cost,
        fused_train_vread_bytes,
    )
    from predictionio_tpu.ops.train_kernel import refusal

    if platform == "tpu":
        return {
            "skipped": "an explicit fused train kernel raises on this TPU",
            "refused": {  # per compute dtype; None = the rule admits it
                cd: refusal(max(n_users, n_items), rank, cd)
                for cd in ("f32", "bf16", "int8")
            },
        }

    peak = PEAKS["TPU v5 lite"]
    out: dict = {
        "shape": {
            "users": n_users, "items": n_items, "ratings": n_ratings,
            "rank": rank,
        },
        "dtypes": {},
    }

    def roofline(flops: float, nbytes: float) -> dict:
        intensity = flops / nbytes
        attainable = min(peak["flops"], intensity * peak["hbm_gbps"])
        return {
            "intensity_flops_per_byte": round(intensity, 3),
            "roofline_mfu": round(attainable / peak["flops"], 4),
            "expected_ms_per_iter": round(
                max(flops / peak["flops"], nbytes / peak["hbm_gbps"]) * 1e3,
                3,
            ),
        }

    # equivalence workload: small enough to train on any mesh in seconds,
    # ragged enough (Zipf) to hit multi-bucket dense shapes
    eq_inter = _make_interactions(
        "zipf", min(n_users, 384), min(n_items, 256), min(n_ratings, 6000)
    )
    f32_vread = fused_train_vread_bytes(n_users, n_items, rank, "f32")
    for cd in ("f32", "bf16", "int8"):
        ref = roofline(
            *als_train_cost_amplified(n_ratings, n_users, n_items, rank)
        )
        fused = roofline(
            *fused_train_cost(n_ratings, n_users, n_items, rank, cd)
        )
        vread = fused_train_vread_bytes(n_users, n_items, rank, cd)
        factors = {}
        for backend in ("reference", "fused"):
            m = train_als(ctx, eq_inter, ALSConfig(
                rank=rank, iterations=2, seed=7, compute_dtype=cd,
                train_kernel=backend,
            ))
            factors[backend] = (m.user_factors, m.item_factors)
        bit_equal = bool(
            np.array_equal(factors["fused"][0], factors["reference"][0])
            and np.array_equal(factors["fused"][1], factors["reference"][1])
        )
        cell = {
            "reference": ref,
            "fused": fused,
            "intensity_gain": round(
                fused["intensity_flops_per_byte"]
                / ref["intensity_flops_per_byte"], 2
            ),
            "vread_bytes": vread,
            "vread_vs_f32": round(vread / f32_vread, 4),
            "factors_bit_equal": bit_equal,
        }
        out["dtypes"][cd] = cell

    # matrix gates: fused analytic intensity STRICTLY above the
    # sector-amplified reference for EVERY compute dtype, the int8
    # one-pass V read ≤ ½ the f32 bytes, and f32 factors bit-equal
    # across backends (bf16/int8 ride the documented-tolerance suite)
    gate = all(
        c["fused"]["intensity_flops_per_byte"]
        > c["reference"]["intensity_flops_per_byte"]
        for c in out["dtypes"].values()
    )
    gate = gate and out["dtypes"]["int8"]["vread_vs_f32"] <= 0.5
    gate = gate and out["dtypes"]["f32"]["factors_bit_equal"]
    out["intensity_gain_f32"] = out["dtypes"]["f32"]["intensity_gain"]
    out["int8_vread_vs_f32"] = out["dtypes"]["int8"]["vread_vs_f32"]
    out["factors_bit_equal_f32"] = out["dtypes"]["f32"]["factors_bit_equal"]
    out["gate_pass"] = bool(gate)
    return out


_FLEET_CHILD = """
import os
from predictionio_tpu.data import store as store_mod
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.query_server import QueryServer
from predictionio_tpu.templates.recommendation import RecommendationEngine

storage = Storage()
store_mod.set_storage(storage)
qs = QueryServer(
    RecommendationEngine.apply(), storage=storage,
    ctx=MeshContext.create(), telemetry=False,
)
qs.start("127.0.0.1", int(os.environ["FLEET_CHILD_PORT"]))
qs.service.serve_forever()
"""


def _fleet_bench(ctx) -> dict:
    """Fleet routing evidence (ISSUE 10): replica scaling (1 vs 3 replica
    qps through the router), hedged vs unhedged p99 with one injected
    slow replica, and a rolling deploy under load.

    The two acceptance numbers are ``hedged_vs_unhedged_p99`` — the hedge
    must at least halve the slow-replica tail — and
    ``roll.client_errors`` — a roll must be invisible to clients (zero
    non-200s).  The slow replica is made slow via the seeded fault shim
    in its own process (``PIO_FAULT_SPEC`` latency on the query path), so
    /readyz stays green and the routers see a wedged-but-listening
    replica, not a dead one.
    """
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.request

    import predictionio_tpu
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db
    from predictionio_tpu.serving.fleet import FleetSupervisor
    from predictionio_tpu.serving.router import ADMITTED, Router
    from predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )
    from predictionio_tpu.tools.loadtest import run_loadtest

    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", 200))
    slow_ms = float(os.environ.get("BENCH_FLEET_SLOW_MS", 250.0))
    slow_p = float(os.environ.get("BENCH_FLEET_SLOW_P", 0.1))
    tmp = tempfile.mkdtemp(prefix="pio-fleet-bench-")
    src = "FLEETB"
    storage_env = {
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": os.path.join(
            tmp, "events.sqlite"
        ),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    }
    old_basedir = os.environ.get("PIO_FS_BASEDIR")
    os.environ["PIO_FS_BASEDIR"] = os.path.join(tmp, "fs")
    routers: list = []
    fleets: list = []
    out: dict = {}
    try:
        storage = Storage(env=storage_env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "fleetbench"))
        le = storage.get_l_events()
        le.init(app_id)
        rng = np.random.default_rng(23)
        events = []
        for u in range(20):
            for i in rng.choice(16, size=6, replace=False):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ))
        le.batch_insert(events, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "fleetbench"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        })
        run_train(engine, ep, "f", storage=storage, ctx=ctx)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
        )
        child_env = dict(os.environ)
        child_env.pop("PIO_FAULT_SPEC", None)
        child_env.update(storage_env)
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["PYTHONPATH"] = os.pathsep.join(
            [repo_root] + ([child_env["PYTHONPATH"]]
                           if child_env.get("PYTHONPATH") else [])
        )

        def spawn_with(extra):
            def spawn(port):
                cenv = dict(child_env)
                cenv.update(extra)
                cenv["FLEET_CHILD_PORT"] = str(port)
                return subprocess.Popen(
                    [sys.executable, "-c", _FLEET_CHILD], env=cenv,
                )
            return spawn

        socks = [socket.socket() for _ in range(4)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        fast_ports, slow_port = ports[:3], ports[3]
        fleet = FleetSupervisor(spawn_with({}), fast_ports)
        slow_spec = (
            f"site=server:queryserver:/queries.json,kind=latency,"
            f"latency_ms={slow_ms:g},p={slow_p:g}"
        )
        slow_fleet = FleetSupervisor(
            spawn_with({"PIO_FAULT_SPEC": slow_spec}), [slow_port]
        )
        fleets = [fleet, slow_fleet]
        fleet.start()
        slow_fleet.start()
        fast_urls = fleet.urls()
        slow_url = slow_fleet.urls()[0]

        def mk_router(urls, hedge):
            r = Router(urls, hedge_enabled=hedge, telemetry=False)
            r.health_interval_ms = 100.0
            r.outlier_ratio = 1e9  # isolate hedging from outlier ejection
            routers.append(r)
            port = r.start("127.0.0.1", 0)
            return r, f"http://127.0.0.1:{port}"

        def wait_proven(r, timeout=180.0):
            t_end = time.time() + timeout
            while time.time() < t_end:
                reps = r.stats()["replicas"]
                if all(x["state"] == ADMITTED
                       and x["generation"] is not None for x in reps):
                    return
                time.sleep(0.1)
            raise TimeoutError("fleet bench replicas never became ready")

        users = [f"u{i}" for i in range(20)]

        def measure(base):
            # run_loadtest appends /queries.json itself
            return run_loadtest(
                base, {"user": "u1", "num": 3},
                requests=n_req, concurrency=8, samples={"user": users},
            )

        r1, b1 = mk_router([fast_urls[0]], hedge=False)
        r3, b3 = mk_router(list(fast_urls), hedge=False)
        mixed = [fast_urls[0], fast_urls[1], slow_url]
        ru, bu = mk_router(mixed, hedge=False)
        rh, bh = mk_router(mixed, hedge=True)
        for r in (r1, r3, ru, rh):
            wait_proven(r)

        one = measure(b1)
        three = measure(b3)
        out["qps_1_replica"] = one["qps"]
        out["qps_3_replicas"] = three["qps"]
        out["scaling_3_over_1"] = (
            round(three["qps"] / one["qps"], 3) if one["qps"] else None
        )
        unhedged = measure(bu)
        hedged = measure(bh)
        out["p99_unhedged_slow_ms"] = unhedged["p99Ms"]
        out["p99_hedged_ms"] = hedged["p99Ms"]
        out["p50_unhedged_slow_ms"] = unhedged["p50Ms"]
        out["p50_hedged_ms"] = hedged["p50Ms"]
        out["hedged_vs_unhedged_p99"] = (
            round(hedged["p99Ms"] / unhedged["p99Ms"], 4)
            if unhedged["p99Ms"] else None
        )
        out["hedges"] = {
            "fired": rh.counters.get("hedges_fired"),
            "won": rh.counters.get("hedges_won"),
            "denied": rh.counters.get("hedges_denied"),
            "delay_ms": round(rh.hedge_delay_ms(), 1),
        }
        out["load_errors"] = (
            one["errors"] + three["errors"]
            + unhedged["errors"] + hedged["errors"]
        )

        # rolling deploy under load: retrain, roll the 3-replica fleet
        # through r3, count every client-visible non-200
        run_train(engine, ep, "f", storage=storage, ctx=ctx)
        fleet.router = r3
        r3.attach_fleet(fleet)
        stop_evt = threading.Event()
        lock = threading.Lock()
        tally = {"ok": 0, "errors": 0}

        def pound(idx):
            i = 0
            while not stop_evt.is_set():
                body = json.dumps(
                    {"user": f"u{(i * 7 + idx) % 20}", "num": 3}
                ).encode()
                req = urllib.request.Request(
                    b3 + "/queries.json", data=body, method="POST",
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                        ok = resp.status == 200
                except Exception:
                    ok = False
                with lock:
                    tally["ok" if ok else "errors"] += 1
                i += 1

        workers = [
            threading.Thread(target=pound, args=(i,), daemon=True)
            for i in range(4)
        ]
        for w in workers:
            w.start()
        t0 = time.time()
        report = fleet.roll()
        wall = time.time() - t0
        stop_evt.set()
        for w in workers:
            w.join(30.0)
        out["roll"] = {
            "wall_sec": round(wall, 1),
            "ok": tally["ok"],
            "client_errors": tally["errors"],
            "replicas_ok": report["ok"],
        }
    finally:
        for r in routers:
            r.stop()
        for f in fleets:
            f.stop()
        store_mod.set_storage(None)
        close_db(os.path.join(tmp, "events.sqlite"))
        if old_basedir is None:
            os.environ.pop("PIO_FS_BASEDIR", None)
        else:
            os.environ["PIO_FS_BASEDIR"] = old_basedir
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _canary_bench(ctx) -> dict:
    """Progressive-delivery evidence (ISSUE 20): a deliberately BAD
    candidate generation (fault-injected latency on exactly that
    generation's serving path) is canaried onto one replica of a
    three-replica fleet under client load.  The controller must detect
    the SLO breach online, auto-roll the canary back to the baseline,
    and write a durable quarantine receipt.

    The gates are: ``rolled_back`` (the candidate was quarantined, not
    promoted), ``client_errors == 0`` (the whole experiment is invisible
    to clients), ``blast_radius`` ≤ the canary fraction plus slack (only
    the one canaried replica's share of traffic ever saw the bad
    generation), and ``receipt_blocks_redeploy`` (after the rollback,
    newest-COMPLETED selection — what every restarted replica runs —
    resolves to the baseline, and a second canary attempt refuses for
    want of a candidate).
    """
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.request

    import predictionio_tpu
    from predictionio_tpu.core import persistence
    from predictionio_tpu.core.workflow import (
        get_latest_completed_instance,
        run_train,
    )
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db
    from predictionio_tpu.serving.canary import CanaryController
    from predictionio_tpu.serving.fleet import FleetSupervisor
    from predictionio_tpu.serving.router import ADMITTED, Router
    from predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )

    slow_ms = float(os.environ.get("BENCH_CANARY_SLOW_MS", 300.0))
    slo_ms = float(os.environ.get("BENCH_CANARY_SLO_MS", 120.0))
    tmp = tempfile.mkdtemp(prefix="pio-canary-bench-")
    src = "CANARYB"
    storage_env = {
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": os.path.join(
            tmp, "events.sqlite"
        ),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    }
    old_basedir = os.environ.get("PIO_FS_BASEDIR")
    os.environ["PIO_FS_BASEDIR"] = os.path.join(tmp, "fs")
    # canary knobs: a short, aggressive window so the bench converges in
    # seconds; the absolute-p99 SLO mode makes the verdict deterministic
    knob_env = {
        "PIO_CANARY_TICK_MS": "100",
        "PIO_CANARY_MIN_SAMPLES": "30",
        "PIO_CANARY_WINDOW_S": "15",
        "PIO_CANARY_P99_SLO_MS": f"{slo_ms:g}",
        "PIO_CANARY_SHADOW_BUDGET": "16",
        "PIO_CANARY_SOAK_S": "2",
    }
    old_knobs = {k: os.environ.get(k) for k in knob_env}
    os.environ.update(knob_env)
    routers: list = []
    fleets: list = []
    canary = None
    out: dict = {}
    try:
        storage = Storage(env=storage_env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "canarybench"))
        le = storage.get_l_events()
        le.init(app_id)
        rng = np.random.default_rng(29)
        events = []
        for u in range(20):
            for i in rng.choice(16, size=6, replace=False):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ))
        le.batch_insert(events, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "canarybench"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        })
        baseline_id = run_train(engine, ep, "f", storage=storage, ctx=ctx)
        candidate_id = run_train(engine, ep, "f", storage=storage, ctx=ctx)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
        )
        child_env = dict(os.environ)
        child_env.pop("PIO_FAULT_SPEC", None)
        child_env.update(storage_env)
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["PYTHONPATH"] = os.pathsep.join(
            [repo_root] + ([child_env["PYTHONPATH"]]
                           if child_env.get("PYTHONPATH") else [])
        )
        # every child: cold-start pinned to the BASELINE (the candidate
        # is newer, so unpinned children would boot straight onto the
        # unverified generation) and carrying the generation-targeted
        # fault — the candidate generation is slow IN WHICHEVER PROCESS
        # serves it, exactly like a model with a real latency regression
        child_env["PIO_PIN_INSTANCE"] = baseline_id
        child_env["PIO_FAULT_SPEC"] = (
            f"site=server:generation:{candidate_id},kind=latency,"
            f"latency_ms={slow_ms:g},p=0.9"
        )

        def spawn(port):
            cenv = dict(child_env)
            cenv["FLEET_CHILD_PORT"] = str(port)
            return subprocess.Popen(
                [sys.executable, "-c", _FLEET_CHILD], env=cenv,
            )

        socks = [socket.socket() for _ in range(3)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        fleet = FleetSupervisor(spawn, ports)
        fleets = [fleet]
        fleet.start()
        router = Router(fleet.urls(), telemetry=False)
        router.health_interval_ms = 100.0
        # the canary controller is the intended responder to a slow
        # generation — don't let latency-outlier ejection race it
        router.outlier_ratio = 1e9
        routers.append(router)
        fleet.router = router
        router.attach_fleet(fleet)
        canary = CanaryController(
            router, fleet=fleet, storage=storage
        )
        router.attach_canary(canary)
        rport = router.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{rport}"

        t_end = time.time() + 180.0
        while time.time() < t_end:
            reps = router.stats()["replicas"]
            if reps and all(
                x["state"] == ADMITTED and x["instanceId"] == baseline_id
                for x in reps
            ):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("canary bench replicas never became ready")

        stop_evt = threading.Event()
        lock = threading.Lock()
        tally = {"ok": 0, "errors": 0}

        def pound(idx):
            i = 0
            while not stop_evt.is_set():
                body = json.dumps(
                    {"user": f"u{(i * 7 + idx) % 20}", "num": 3}
                ).encode()
                req = urllib.request.Request(
                    base + "/queries.json", data=body, method="POST",
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                        ok = resp.status == 200
                except Exception:
                    ok = False
                with lock:
                    tally["ok" if ok else "errors"] += 1
                i += 1

        workers = [
            threading.Thread(target=pound, args=(i,), daemon=True)
            for i in range(4)
        ]
        for w in workers:
            w.start()
        t0 = time.time()
        canary.start_canary()
        while canary.active() and time.time() - t0 < 120.0:
            time.sleep(0.2)
        wall = time.time() - t0
        stop_evt.set()
        for w in workers:
            w.join(30.0)

        stats = canary.stats()
        outcome = stats.get("lastOutcome") or {}
        gens = router.generation_stats()
        cand = gens.get(candidate_id) or {}
        attributed = sum(
            g.get("requests", 0) for g in gens.values()
        )
        blast = (
            cand.get("requests", 0) / attributed if attributed else None
        )
        blocks = get_latest_completed_instance(storage).id == baseline_id
        try:
            canary.start_canary()
            second_refused = False
            canary.request_abort()
        except ValueError:
            second_refused = True
        out = {
            "baseline": baseline_id,
            "candidate": candidate_id,
            "wall_sec": round(wall, 1),
            "rolled_back": outcome.get("outcome") == "quarantined"
            and outcome.get("candidate") == candidate_id,
            "rollback_reason": outcome.get("reason"),
            "client_ok": tally["ok"],
            "client_errors": tally["errors"],
            "blast_radius": round(blast, 4) if blast is not None else None,
            "candidate_requests": cand.get("requests", 0),
            "candidate_p99_ms": cand.get("p99Ms"),
            "shadow_pairs": (stats.get("shadow") or {}).get("pairs", 0),
            "quarantined": stats.get("quarantined"),
            "receipt_on_disk": persistence.is_quarantined(candidate_id),
            "selection_resolves_baseline": blocks,
            "second_canary_refused": second_refused,
            "receipt_blocks_redeploy": blocks and second_refused,
        }
    finally:
        if canary is not None:
            canary.stop()
        for r in routers:
            r.stop()
        for f in fleets:
            f.stop()
        for k, v in old_knobs.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        store_mod.set_storage(None)
        close_db(os.path.join(tmp, "events.sqlite"))
        if old_basedir is None:
            os.environ.pop("PIO_FS_BASEDIR", None)
        else:
            os.environ["PIO_FS_BASEDIR"] = old_basedir
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _elastic_bench(ctx) -> dict:
    """Elastic fleet evidence (ISSUE 11): a flash-crowd scenario (10x
    step) replayed against an autoscaled two-replica fleet, with a
    seeded ``crash:fleet:replica`` preemption fired mid-surge and a
    scale-down drain after the crowd passes.

    The gate is "SLO held while scaling": zero client-visible errors
    across the whole program (shed 503s are the backpressure contract,
    not errors), flash-phase p99 within ``BENCH_ELASTIC_SLO_P99_MS``,
    at least one scale-up AND one scale-down actually executed, and the
    preemption plan actually fired (a chaos run where the kill never
    landed proves nothing).
    """
    import shutil
    import socket
    import tempfile
    import threading

    import predictionio_tpu
    from predictionio_tpu.common import faults as _faults
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db
    from predictionio_tpu.serving.autoscaler import Autoscaler
    from predictionio_tpu.serving.fleet import (
        PREEMPT_SITE, FleetSupervisor,
    )
    from predictionio_tpu.serving.router import ADMITTED, Router
    from predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )
    from predictionio_tpu.tools.scenarios import (
        parse_scenario, run_scenario,
    )

    rate = float(os.environ.get("BENCH_ELASTIC_RATE", 25.0))
    slo_p99_ms = float(os.environ.get("BENCH_ELASTIC_SLO_P99_MS", 1500.0))
    slow_ms = float(os.environ.get("BENCH_ELASTIC_SLOW_MS", 40.0))
    tmp = tempfile.mkdtemp(prefix="pio-elastic-bench-")
    src = "ELASTB"
    storage_env = {
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": os.path.join(
            tmp, "events.sqlite"
        ),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    }
    old_basedir = os.environ.get("PIO_FS_BASEDIR")
    os.environ["PIO_FS_BASEDIR"] = os.path.join(tmp, "fs")
    routers: list = []
    fleets: list = []
    scalers: list = []
    timers: list = []
    out: dict = {}
    try:
        storage = Storage(env=storage_env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "elasticbench"))
        le = storage.get_l_events()
        le.init(app_id)
        rng = np.random.default_rng(29)
        events = []
        for u in range(20):
            for i in rng.choice(16, size=6, replace=False):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ))
        le.batch_insert(events, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "elasticbench"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        })
        run_train(engine, ep, "e", storage=storage, ctx=ctx)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
        )
        child_env = dict(os.environ)
        child_env.pop("PIO_FAULT_SPEC", None)
        child_env.update(storage_env)
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["PYTHONPATH"] = os.pathsep.join(
            [repo_root] + ([child_env["PYTHONPATH"]]
                           if child_env.get("PYTHONPATH") else [])
        )
        # a touch of injected latency so in-flight pressure accumulates
        # at flash rates (a rank-4 CPU model otherwise answers too fast
        # for inflight utilization to register)
        child_env["PIO_FAULT_SPEC"] = (
            f"site=server:queryserver:/queries.json,kind=latency,"
            f"latency_ms={slow_ms:g},p=1"
        )

        def spawn(port):
            cenv = dict(child_env)
            cenv["FLEET_CHILD_PORT"] = str(port)
            return subprocess.Popen(
                [sys.executable, "-c", _FLEET_CHILD], env=cenv,
            )

        socks = [socket.socket() for _ in range(2)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()

        r = Router(
            [f"http://127.0.0.1:{p}" for p in ports],
            hedge_enabled=False, telemetry=False,
        )
        r.health_interval_ms = 100.0
        r.outlier_ratio = 1e9
        # 24 open-loop workers against a 24-per-replica cap: one healthy
        # replica can absorb the whole crowd at the cap boundary, so a
        # mid-surge preemption retries cleanly instead of 502ing
        r.replica_max_inflight = 24
        routers.append(r)
        rport = r.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{rport}"

        fleet = FleetSupervisor(spawn, ports, router=r)
        fleets.append(fleet)
        r.attach_fleet(fleet)
        fleet.start()

        t_end = time.time() + 180.0
        while time.time() < t_end:
            reps = r.stats()["replicas"]
            if reps and all(x["state"] == ADMITTED
                            and x["generation"] is not None for x in reps):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("elastic bench replicas never became ready")

        scaler = Autoscaler(r, fleet)
        scaler.interval_ms = 300.0
        scaler.min_replicas = 2
        scaler.max_replicas = 3
        scaler.up_threshold = 0.2
        scaler.down_threshold = 0.1
        scaler.up_cooldown_s = 1.0
        scaler.down_cooldown_s = 2.0
        scaler.down_after = 3
        scaler.busy_enabled = False  # telemetry=False children: no /metrics
        scalers.append(scaler)
        r.attach_autoscaler(scaler)
        scaler.start()

        program = parse_scenario(
            f"steady:name=calm,rate={rate:g},duration=6;"
            f"flash:name=flash,base={rate:g},peak={rate * 10:g},"
            f"at=2,hold=8,duration=12;"
            f"steady:name=cooldown,rate={rate:g},duration=8"
        )
        # the preemption: a seeded kill -9 of one replica, installed on
        # a timer so it lands mid-flash while the scaler is growing the
        # fleet (the supervisor's monitor consults the site every 0.25s)
        plan = _faults.FaultPlan(
            [_faults.FaultRule(site=PREEMPT_SITE, kind="crash", times=1)],
            seed=7,
        )
        preempt_timer = threading.Timer(10.0, _faults.install, args=(plan,))
        preempt_timer.daemon = True
        timers.append(preempt_timer)
        preempt_timer.start()

        users = [f"u{i}" for i in range(20)]
        res = run_scenario(
            base, {"user": "u1", "num": 3}, program,
            samples={"user": users}, concurrency=24,
            slo_p99_ms=slo_p99_ms,
        )

        # the crowd has passed: give the scaler a moment to drain the
        # surge replica back out (down_after low ticks + cooldown)
        t_end = time.time() + 30.0
        while time.time() < t_end:
            if scaler.stats()["scaleDowns"] >= 1:
                break
            time.sleep(0.25)

        stats = scaler.stats()
        fired = sum(x["fired"] for x in plan.stats()["rules"])
        flash = next(
            (p for p in res["phases"] if p["name"] == "flash"),
            res["phases"][1],
        )
        out["phases"] = [
            {
                "name": p["name"],
                "offered": p["offered"],
                "ok": p["ok"],
                "errors": p["errors"],
                "shed": p["shed"],
                "p50_ms": p["p50Ms"],
                "p99_ms": p["p99Ms"],
            }
            for p in res["phases"]
        ]
        out["client_errors"] = res["errors"]
        out["shed"] = res["shed"]
        out["p99_while_scaling_ms"] = flash["p99Ms"]
        out["slo_p99_ms"] = slo_p99_ms
        out["worst_lag_s"] = res["worstLagS"]
        out["scale_ups"] = stats["scaleUps"]
        out["scale_downs"] = stats["scaleDowns"]
        out["preemptions"] = fired
        out["fleet_transitions"] = fleet.status()["transitions"]
        out["gate_pass"] = bool(
            res["errors"] == 0
            and (flash["p99Ms"] or 0.0) <= slo_p99_ms
            and stats["scaleUps"] >= 1
            and stats["scaleDowns"] >= 1
            and fired >= 1
        )
    finally:
        for t in timers:
            t.cancel()
        _faults.clear()
        for sc in scalers:
            sc.stop()
        for r in routers:
            r.stop()
        for f in fleets:
            f.stop()
        store_mod.set_storage(None)
        close_db(os.path.join(tmp, "events.sqlite"))
        if old_basedir is None:
            os.environ.pop("PIO_FS_BASEDIR", None)
        else:
            os.environ["PIO_FS_BASEDIR"] = old_basedir
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _freshness_bench(ctx) -> dict:
    """Streaming-freshness evidence: sustained query load against an
    autoscaled two-replica fleet while the in-process event plane folds
    committed events into sealed micro-generation deltas and the router
    propagates each one to every replica.

    Three numbers matter: ``visible_p99_ms`` (event submitted →
    prediction-visible on every replica, i.e. WAL ack + group-commit +
    fold-in + seal + router push + in-place apply), ``apply_wall_ms``
    (the router→fleet propagation round-trip alone), and
    ``lost_acked_events`` (must be zero — every fast-acked event id is
    found back in storage after the run).  The gate is all of: every
    batch sealed, every push acked by the full fleet, visible p99 within
    ``PIO_FRESHNESS_SLO_MS``, zero lost acked events, zero client-visible
    query errors while the deltas landed.
    """
    import copy as _copy
    import shutil
    import socket
    import tempfile
    import threading
    import urllib.request as _urlreq

    import predictionio_tpu
    from predictionio_tpu.core import delta as _delta
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.api.event_server import EventServer
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db
    from predictionio_tpu.serving.autoscaler import Autoscaler
    from predictionio_tpu.serving.fleet import FleetSupervisor
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.serving.router import ADMITTED, Router
    from predictionio_tpu.templates.recommendation import (
        RecommendationEngine,
    )

    batches = int(os.environ.get("BENCH_FRESHNESS_BATCHES", 10))
    per_batch = int(os.environ.get("BENCH_FRESHNESS_EVENTS", 24))
    slo_ms = float(os.environ.get("PIO_FRESHNESS_SLO_MS", "5000"))
    tmp = tempfile.mkdtemp(prefix="pio-freshness-bench-")
    src = "FRESHB"
    storage_env = {
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": os.path.join(
            tmp, "events.sqlite"
        ),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    }
    saved_env = {
        k: os.environ.get(k)
        for k in ("PIO_FS_BASEDIR", "PIO_STREAMING", "PIO_DELTA_DIR",
                  "PIO_DELTA_CATCHUP_MS")
    }
    os.environ["PIO_FS_BASEDIR"] = os.path.join(tmp, "fs")
    os.environ["PIO_STREAMING"] = "1"
    os.environ["PIO_DELTA_DIR"] = os.path.join(tmp, "deltas")
    # visibility is router-push driven here; park the replica poll pace
    # so catch-up slack never flatters the measurement
    os.environ["PIO_DELTA_CATCHUP_MS"] = "60000"
    routers: list = []
    fleets: list = []
    scalers: list = []
    event_servers: list = []
    stop_load = threading.Event()
    load_threads: list = []
    out: dict = {}
    try:
        storage = Storage(env=storage_env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "freshbench"))
        le = storage.get_l_events()
        le.init(app_id)
        rng = np.random.default_rng(31)
        events = []
        for u in range(20):
            for i in rng.choice(16, size=6, replace=False):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ))
        le.batch_insert(events, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "freshbench"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        })
        run_train(engine, ep, "fresh", storage=storage, ctx=ctx)

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(predictionio_tpu.__file__))
        )
        child_env = dict(os.environ)
        child_env.pop("PIO_FAULT_SPEC", None)
        child_env.update(storage_env)
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["PYTHONPATH"] = os.pathsep.join(
            [repo_root] + ([child_env["PYTHONPATH"]]
                           if child_env.get("PYTHONPATH") else [])
        )

        def spawn(port):
            cenv = dict(child_env)
            cenv["FLEET_CHILD_PORT"] = str(port)
            return subprocess.Popen(
                [sys.executable, "-c", _FLEET_CHILD], env=cenv,
            )

        socks = [socket.socket() for _ in range(2)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()

        r = Router(
            [f"http://127.0.0.1:{p}" for p in ports],
            hedge_enabled=False, telemetry=False,
        )
        r.health_interval_ms = 100.0
        r.outlier_ratio = 1e9
        routers.append(r)
        rport = r.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{rport}"

        fleet = FleetSupervisor(spawn, ports, router=r)
        fleets.append(fleet)
        r.attach_fleet(fleet)
        fleet.start()

        t_end = time.time() + 180.0
        while time.time() < t_end:
            reps = r.stats()["replicas"]
            if reps and all(x["state"] == ADMITTED
                            and x["generation"] is not None for x in reps):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("freshness bench replicas never became ready")

        # the scaler runs for real (evaluates every tick) but the rank-4
        # CPU workload keeps utilization far under the threshold, so the
        # fleet holds steady and every push can be gated on full-fleet
        # acknowledgement
        scaler = Autoscaler(r, fleet)
        scaler.interval_ms = 300.0
        scaler.min_replicas = 2
        scaler.max_replicas = 3
        scaler.up_threshold = 0.9
        scaler.busy_enabled = False  # telemetry=False children: no /metrics
        scalers.append(scaler)
        r.attach_autoscaler(scaler)
        scaler.start()

        # event plane: its own copy of the SAME deployed base generation
        # the children serve, loaded through the identical deploy path so
        # the delta fence (base fingerprint) matches across processes
        qs_local = QueryServer(
            engine, storage=storage, ctx=ctx, telemetry=False,
        )
        st_local = qs_local._streaming
        if st_local is None:
            raise RuntimeError("PIO_STREAMING=1 but streaming not enabled")
        pub_model = _copy.deepcopy(st_local["model"])
        delta_dir = st_local["dir"]
        qs_local.stop()

        es = EventServer(
            storage=storage, ingest_mode="fast",
            wal_dir=os.path.join(tmp, "wal"),
            ingest_flush_ms=5.0, telemetry=False,
        )
        event_servers.append(es)
        # gate off: this bench measures the pipeline's latency, not
        # fold-in quality (the quality gate has its own chaos coverage)
        pub = es.enable_delta_publisher(pub_model, min_overlap=0.0)
        if pub is None:
            raise RuntimeError("delta publisher did not enable")

        load_counts = {"ok": 0, "errors": 0}
        count_lock = threading.Lock()

        def _load(worker):
            i = worker
            while not stop_load.is_set():
                i += 1
                body = json.dumps(
                    {"user": f"u{i % 20}", "num": 3}
                ).encode()
                req = _urlreq.Request(
                    base + "/queries.json", data=body,
                    headers={"Content-Type": "application/json"},
                )
                try:
                    with _urlreq.urlopen(req, timeout=10) as resp:
                        resp.read()
                        ok = resp.status == 200
                except Exception:
                    ok = False
                with count_lock:
                    load_counts["ok" if ok else "errors"] += 1
                time.sleep(0.01)

        for w in range(4):
            t = threading.Thread(target=_load, args=(w,), daemon=True)
            load_threads.append(t)
            t.start()

        log = _delta.DeltaLog(delta_dir)
        acked_ids: list = []
        visible_ms: list = []
        apply_ms: list = []
        seal_failures = 0
        partial_pushes = 0
        seq = 0
        erng = np.random.default_rng(41)
        for _ in range(batches):
            t0 = time.time()
            for _e in range(per_batch):
                seq += 1
                ev = Event(
                    event="rate", entity_type="user",
                    entity_id=f"u{erng.integers(20)}",
                    target_entity_type="item",
                    target_entity_id=f"i{erng.integers(16)}",
                    properties={"rating": float(erng.integers(1, 6))},
                    event_id=f"fresh-{seq:05d}",
                )
                es.ingest_buffer.submit(ev, app_id)  # WAL fast-ack
                acked_ids.append(ev.event_id)
            # the group-commit flush feeds the publisher within ~flush_ms
            t_wait = time.time() + 30.0
            while pub.pending() < per_batch and time.time() < t_wait:
                time.sleep(0.002)
            receipt = pub.flush()
            if not (receipt and receipt.get("sealed")):
                seal_failures += 1
                continue
            blob = open(log.path(receipt["epoch"]), "rb").read()
            t_push = time.time()
            acks = r.push_delta(blob)
            now = time.time()
            apply_ms.append((now - t_push) * 1000.0)
            visible_ms.append((now - t0) * 1000.0)
            if acks["acked"] != acks["replicas"]:
                partial_pushes += 1
        stop_load.set()
        for t in load_threads:
            t.join(timeout=15.0)

        # zero-loss audit: every fast-acked event id must be in storage
        stored = {e.event_id for e in le.find(app_id)}
        lost = [i for i in acked_ids if i not in stored]
        vis = sorted(visible_ms)
        p99 = vis[min(len(vis) - 1, int(len(vis) * 0.99))] if vis else None
        pstats = pub.stats()
        out = {
            "batches": batches,
            "events_per_batch": per_batch,
            "sealed": pstats["sealed"],
            "seal_failures": seal_failures,
            "partial_pushes": partial_pushes,
            "visible_p99_ms": round(p99, 2) if p99 is not None else None,
            "visible_max_ms": round(vis[-1], 2) if vis else None,
            "apply_wall_ms": (
                round(sorted(apply_ms)[len(apply_ms) // 2], 2)
                if apply_ms else None
            ),
            "slo_ms": slo_ms,
            "lost_acked_events": len(lost),
            "query_ok": load_counts["ok"],
            "query_errors": load_counts["errors"],
            "scale_ups": scaler.stats()["scaleUps"],
            "gate_pass": bool(
                pstats["sealed"] == batches
                and seal_failures == 0
                and partial_pushes == 0
                and p99 is not None
                and p99 <= slo_ms
                and not lost
                and load_counts["errors"] == 0
            ),
        }
    finally:
        stop_load.set()
        for t in load_threads:
            t.join(timeout=5.0)
        for es in event_servers:
            es.stop()
        for sc in scalers:
            sc.stop()
        for r in routers:
            r.stop()
        for f in fleets:
            f.stop()
        store_mod.set_storage(None)
        close_db(os.path.join(tmp, "events.sqlite"))
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _sharded_serving_bench(ctx) -> dict:
    """Sharded-serving evidence (ISSUE 12): on the multi-device mesh, a
    catalog deliberately sized past one device's (simulated) HBM budget is
    served through the :class:`ShardingPlan` partitioned fast path under a
    Zipf workload.

    Gates: (a) the catalog really overflows the per-device budget while
    every shard's resident block fits it, (b) sharded answers are
    BIT-IDENTICAL to the replicated reference (indices and values), (c)
    per-shard utilization is non-null, and (d) the popularity-aware plan's
    max/min attributed busy-fraction balance stays ≤ 1.5.  The naive
    round-robin plan serves the same workload and reports its balance for
    comparison, ungated — with hot items at contiguous low ids it can land
    anywhere; the LPT plan cannot.
    """
    from predictionio_tpu.serving import sharding as sharding_mod
    from predictionio_tpu.serving.fastpath import BucketedScorer

    n_items = int(os.environ.get("BENCH_SHARD_ITEMS", 4096))
    rank = int(os.environ.get("BENCH_SHARD_RANK", 16))
    budget = int(os.environ.get("BENCH_SHARD_BUDGET", 70_000))
    n_req = int(os.environ.get("BENCH_SHARD_REQUESTS", 640))
    n_users = 512
    k = 20
    rng = np.random.default_rng(12)
    U = rng.normal(size=(n_users, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    catalog_bytes = int(V.nbytes)
    users = _sample_ids(rng, n_users, n_req, "zipf", s=1.1)

    # replicated reference: the ground truth answers AND the measured
    # per-item win counts the popularity plan balances (the live analogue
    # of the publish-time factor-norm proxy)
    repl = BucketedScorer(ctx, U, V, max_k=k, sharding="replicated")
    ref_idx, ref_val = repl.score_topk(users, k)
    wins = np.bincount(
        ref_idx.reshape(-1), minlength=n_items
    ).astype(np.float64)

    n_shards = sharding_mod.shard_count_for_budget(
        n_items, rank * 4.0, budget
    )
    plans = {
        name: sharding_mod.build_plan(
            n_items, n_shards, weights=wins, strategy=name,
            capacity_budget_bytes=budget,
        )
        for name in ("popularity", "round_robin")
    }
    per_plan: dict = {}
    exact = True
    busy_ok = True
    resident_fits = True
    for name, plan in plans.items():
        sc = BucketedScorer(ctx, U, V, max_k=k, plan=plan, sharding="sharded")
        idx, vals = sc.score_topk(users, k)
        eq = bool(
            np.array_equal(idx, ref_idx) and np.array_equal(vals, ref_val)
        )
        exact = exact and eq
        st = (sc.stats() or {}).get("sharding") or {}
        busy = st.get("busy_fraction")
        busy_ok = busy_ok and bool(
            busy and all(b is not None for b in busy)
        )
        resident = st.get("resident_bytes") or []
        resident_fits = resident_fits and bool(
            resident and max(resident) <= budget
        )
        balance = (
            round(max(busy) / min(busy), 4)
            if busy and min(busy) > 0 else None
        )
        per_plan[name] = {
            "fingerprint": plan.fingerprint,
            "exact_match": eq,
            "busy_fraction": busy,
            "busy_balance": balance,
            "result_share": st.get("result_share"),
            "resident_bytes_per_shard": resident,
            "merge_bytes": st.get("merge_bytes"),
        }
    pop_balance = per_plan["popularity"]["busy_balance"]
    return {
        "n_items": n_items,
        "rank": rank,
        "k": k,
        "requests": int(n_req),
        "distribution": "zipf",
        "catalog_bytes": catalog_bytes,
        "per_device_budget_bytes": budget,
        "n_shards": n_shards,
        "plans": per_plan,
        "gate_pass": bool(
            catalog_bytes > budget
            and n_shards > 1
            and resident_fits
            and exact
            and busy_ok
            and pop_balance is not None
            and pop_balance <= 1.5
        ),
    }


_POD_BENCH_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import json
import numpy as np
from predictionio_tpu.parallel import distributed

assert distributed.initialize()
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving import sharding as _sharding
from predictionio_tpu.serving.fastpath import BucketedScorer

ctx = MeshContext.create()
rng = np.random.default_rng({seed})
U = rng.standard_normal(({n_users}, {rank})).astype(np.float32)
V = rng.standard_normal(({n_items}, {rank})).astype(np.float32)
batches = [rng.integers(0, {n_users}, n).astype(np.int32) for n in (1, 13)]
plan = _sharding.build_plan({n_items}, 4, host_groups=2)
sc = BucketedScorer(ctx, U, V, max_k={k}, buckets=(1, 8),
                    sharding="sharded", plan=plan)
cells = []
for users in batches:
    idx, vals = sc.score_topk(users, {k})
    cells.append({{"idx": np.asarray(idx).tolist(),
                  "vals": np.asarray(vals, np.float64).tolist()}})
st = sc.stats()
pod = st["pod"]
shard = (st.get("sharding") or {{}})
print("POD_RESULT " + json.dumps({{
    "cells": cells,
    "pod_bytes": pod["cross_host_merge_bytes"],
    "pod_seconds": pod["cross_host_merge_seconds"],
    "dispatches": pod["dispatches"],
    "on_host_merge_bytes": shard.get("merge_bytes"),
    "host_groups": pod["host_groups"],
    "process_count": pod["process_count"],
}}))
"""


def _pod_serving_bench() -> dict:
    """Pod-scale serving gate (ISSUE 18): a REAL 2-process
    ``jax.distributed`` CPU mesh (Gloo collectives, 2 virtual devices per
    process) serves a 4-shard / 2-host-group plan through the two-tier
    merge, and the parent replays the same workload on a single-process
    replicated scorer.

    Gates: (a) the pod answers are BIT-identical to the replicated
    reference — indices and values, every dispatch; (b) the measured
    cross-host merge traffic is <= the ``H*B*k*8`` two-tier derivation in
    docs/perf_roofline.md (it lands exactly on it; the bound keeps the
    gate honest if accounting grows).  The flat single-tier collective
    would have moved ``S*B*local_k*8`` across hosts — the reduction
    factor is reported alongside.
    """
    import socket
    import subprocess
    import tempfile

    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving.fastpath import BucketedScorer

    n_users, n_items, rank, k, seed = 40, 320, 8, 10, 11
    script = _POD_BENCH_WORKER.format(
        repo=os.path.dirname(os.path.abspath(__file__)),
        seed=seed, n_users=n_users, n_items=n_items, rank=rank, k=k,
    )
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord_port = s.getsockname()[1]
    with tempfile.TemporaryDirectory(prefix="pio-pod-bench-") as tmp:
        path = os.path.join(tmp, "pod_worker.py")
        with open(path, "w") as f:
            f.write(script)
        procs = []
        for pid in (0, 1):
            env = dict(os.environ)
            env.update(
                PIO_COORDINATOR=f"127.0.0.1:{coord_port}",
                PIO_NUM_PROCESSES="2",
                PIO_PROCESS_ID=str(pid),
            )
            procs.append(subprocess.Popen(
                [sys.executable, path], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            ))
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=240)
                outs.append(out)
                if p.returncode != 0:
                    raise RuntimeError(f"pod bench worker failed:\n{out}")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    got = json.loads(next(
        ln for ln in outs[0].splitlines() if ln.startswith("POD_RESULT ")
    )[len("POD_RESULT "):])

    # replicated reference on the parent's own mesh, same seeded workload
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    batches = [rng.integers(0, n_users, n).astype(np.int32) for n in (1, 13)]
    repl = BucketedScorer(
        MeshContext.create(), U, V, max_k=k, buckets=(1, 8),
        sharding="replicated",
    )
    exact = True
    for cell, users in zip(got["cells"], batches):
        ref_idx, ref_val = repl.score_topk(users, k)
        exact = exact and bool(
            np.array_equal(np.asarray(cell["idx"], np.int32), ref_idx)
            and np.array_equal(
                np.asarray(cell["vals"], np.float64),
                np.asarray(ref_val, np.float64),
            )
        )
    # two-tier derivation (docs/perf_roofline.md): H*b*k*8 per dispatch
    # over the padded rungs b = 1, 8, 8; flat would be S*b*local_k*8
    h, s_shards, local_k = 2, 4, k
    rungs = (1, 8, 8)
    derived = float(sum(h * b * k * 8 for b in rungs))
    flat = float(sum(s_shards * b * local_k * 8 for b in rungs))
    measured = float(got["pod_bytes"])
    return {
        "processes": int(got["process_count"]),
        "host_groups": int(got["host_groups"]),
        "n_shards": s_shards,
        "k": k,
        "dispatches": int(got["dispatches"]),
        "exact_match": exact,
        "cross_host_merge_bytes": measured,
        "cross_host_merge_bytes_derived": derived,
        "flat_merge_bytes": flat,
        "reduction_factor": round(flat / measured, 4) if measured else None,
        "cross_host_merge_seconds": got["pod_seconds"],
        "on_host_merge_bytes": got["on_host_merge_bytes"],
        "gate_pass": bool(exact and measured and measured <= derived),
    }


def _retrieval_bench(ctx, platform) -> dict:
    """IVF retrieval gate (ISSUE 16): serve a clustered catalog through the
    coarse-partition fast path at the DEFAULT nprobe and prove the two
    halves of the trade hold at once — recall@10 >= 0.95 against the exact
    scorer AND mean scanned fraction <= 0.2 of the catalog's padded rows.

    The catalog is a Gaussian mixture, not white noise: IVF prunes
    *structure*, and a structureless catalog has nothing to prune (every
    cluster holds someone's top-k, so recall collapses at any scanned
    fraction < 1).  Real item-factor matrices cluster — genre, popularity
    band, co-consumption — and the mixture encodes that regime.

    Recall is measured over b=1 dispatches, where the probe budget is the
    per-query ``nprobe`` itself (no batch widening) — the same regime the
    publish-time refusal gate measures.  The scanned fraction comes from
    the scorer's own accounting (``stats()['retrieval']``), read BEFORE
    any batched timing dispatches so wide rungs' widened probe budgets
    don't dilute it.  Wall-clock scores/s is recorded on TPU only: off
    TPU the fused kernel runs under the Pallas interpreter, whose timings
    are meaningless.
    """
    from predictionio_tpu.core.evaluation import recall_at_k
    from predictionio_tpu.ops import ivf as ivf_mod
    from predictionio_tpu.serving.fastpath import BucketedScorer

    n_items = int(os.environ.get("BENCH_IVF_ITEMS", 8192))
    rank = int(os.environ.get("BENCH_IVF_RANK", 16))
    nlist = int(os.environ.get("BENCH_IVF_NLIST", 64))
    n_queries = int(os.environ.get("BENCH_IVF_QUERIES", 96))
    k = 10
    rng = np.random.default_rng(16)
    centers = (rng.normal(size=(nlist, rank)) * 4.0).astype(np.float32)
    item_cluster = rng.integers(0, nlist, size=n_items)
    V = (
        centers[item_cluster] + rng.normal(size=(n_items, rank)) * 0.25
    ).astype(np.float32)
    # queries live near the same centers: each user's top-k concentrates
    # in a handful of clusters, the regime the nprobe default targets
    q_cluster = rng.integers(0, nlist, size=n_queries)
    U = (
        centers[q_cluster] + rng.normal(size=(n_queries, rank)) * 0.25
    ).astype(np.float32)

    index = ivf_mod.build_index(V, nlist)  # default nprobe = nlist // 8
    exact_sc = BucketedScorer(ctx, U, V, max_k=k)
    ivf_sc = BucketedScorer(
        ctx, U, V, max_k=k, ivf_index=index, retrieval="ivf"
    )
    exact_rows = []
    approx_rows = []
    for u in range(n_queries):
        one = np.array([u])
        exact_rows.append(exact_sc.score_topk(one, k)[0][0])
        approx_rows.append(ivf_sc.score_topk(one, k)[0][0])
    recall = recall_at_k(np.stack(exact_rows), np.stack(approx_rows), k)
    st = (ivf_sc.stats() or {}).get("retrieval") or {}
    frac = st.get("scanned_fraction")

    measured = None
    if platform == "tpu":  # never time the Pallas interpreter
        users_all = np.arange(n_queries)
        exact_sc.score_topk(users_all, k)  # warm the wide rung
        ivf_sc.score_topk(users_all, k)
        reps = int(os.environ.get("BENCH_IVF_REPS", 20))
        t0 = time.perf_counter()
        for _ in range(reps):
            exact_sc.score_topk(users_all, k)
        t_exact = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            ivf_sc.score_topk(users_all, k)
        t_ivf = time.perf_counter() - t0
        scored = reps * n_queries * n_items
        measured = {
            "reps": reps,
            "exact_scores_per_s": round(scored / t_exact, 1),
            "ivf_requests_per_s": round(reps * n_queries / t_ivf, 1),
            "speedup_vs_exact": round(t_exact / t_ivf, 4),
        }
    return {
        "n_items": n_items,
        "rank": rank,
        "k": k,
        "queries": n_queries,
        "nlist": int(st.get("nlist") or index.nlist),
        "nprobe": int(st.get("nprobe") or index.nprobe),
        "min_probes": st.get("min_probes"),
        "cap_pad": st.get("cap_pad"),
        "recall_at_10": round(float(recall), 4),
        "scanned_fraction": frac,
        "analytic_scan_speedup": (
            round(1.0 / frac, 2) if frac else None
        ),
        "fingerprint": st.get("fingerprint"),
        "measured": measured,
        "gate_pass": bool(
            recall >= 0.95 and frac is not None and frac <= 0.2
        ),
    }


def _tenant_bench(ctx) -> dict:
    """Multi-tenant QoS + composed-pipeline evidence (ISSUE 19).

    Two gates in one block:

    * ``noisy_neighbor`` — two tenants behind one query server; tenant
      ``alpha`` drives far past its qps quota while ``beta`` sends a
      modest stream.  The contract: alpha's overage is shed with 503s
      ATTRIBUTED to its quota (token bucket, ``Retry-After``), alpha's
      admitted requests all succeed, and beta sees zero errors, zero
      sheds, and a p99 inside its SLO — one tenant's saturation must
      not tax another's latency.
    * ``pipeline`` — the same query answered two ways on a bench-sized
      clustered catalog: single-stage exact ALS (full-catalog matvec +
      top-k) vs the composed IVF-retrieval → fused-ALS-ranking
      pipeline.  The gate is the ISSUE's bar: the pipeline beats exact
      on scores/s (catalog rows ranked per wall-second) at <= 1.5x the
      exact path's p99.
    """
    import shutil
    import tempfile
    import threading
    import types

    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.data.storage.sqlite import close_db
    from predictionio_tpu.models.als import ALSModel, ALSScorer
    from predictionio_tpu.ops import ivf as ivf_mod
    from predictionio_tpu.serving.pipeline import (
        PipelineConfig, StageSpec, build_recommendation_stages,
    )
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.serving.tenancy import TenantRegistry, TenantSpec
    from predictionio_tpu.templates.recommendation import (
        Query, RecommendationEngine,
    )
    from predictionio_tpu.tools.loadtest import run_loadtest

    quota_qps = float(os.environ.get("BENCH_TENANT_QUOTA_QPS", 25.0))
    slo_ms = float(os.environ.get("BENCH_TENANT_SLO_MS", 500.0))
    out: dict = {}

    # -- noisy neighbor: quota shed + isolation ---------------------------
    tmp = tempfile.mkdtemp(prefix="pio-tenant-bench-")
    src = "TENB"
    storage_env = {
        f"PIO_STORAGE_SOURCES_{src}_TYPE": "sqlite",
        f"PIO_STORAGE_SOURCES_{src}_PATH": os.path.join(
            tmp, "events.sqlite"
        ),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": src,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": src,
    }
    old_basedir = os.environ.get("PIO_FS_BASEDIR")
    os.environ["PIO_FS_BASEDIR"] = os.path.join(tmp, "fs")
    qs = None
    try:
        storage = Storage(env=storage_env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "tenantbench"))
        le = storage.get_l_events()
        le.init(app_id)
        rng = np.random.default_rng(19)
        events = []
        for u in range(20):
            for i in rng.choice(16, size=6, replace=False):
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                ))
        le.batch_insert(events, app_id)
        engine = RecommendationEngine.apply()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "tenantbench"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        })
        run_train(engine, ep, "e", storage=storage, ctx=ctx)

        registry = TenantRegistry(
            [
                TenantSpec("alpha", "bench-key-alpha", weight=1.0,
                           quota_qps=quota_qps, slo_ms=slo_ms),
                TenantSpec("beta", "bench-key-beta", weight=1.0,
                           slo_ms=slo_ms),
            ],
            total_inflight=64,
        )
        qs = QueryServer(
            engine, storage=storage, ctx=ctx, telemetry=False,
            tenants=registry,
        )
        port = qs.start("127.0.0.1", 0)
        url = f"http://127.0.0.1:{port}"
        users = [f"u{i}" for i in range(20)]

        results: dict = {}

        def drive(name, key, requests, concurrency):
            results[name] = run_loadtest(
                url, {"num": 3, "accessKey": key},
                requests=requests, concurrency=concurrency,
                samples={"user": users},
            )

        # alpha floods (8 closed-loop workers against a ~sub-ms model
        # burn the banked burst tokens in well under a second); beta
        # keeps a polite trickle going the whole time
        ta = threading.Thread(
            target=drive, args=("alpha", "bench-key-alpha", 400, 8),
        )
        tb = threading.Thread(
            target=drive, args=("beta", "bench-key-beta", 120, 2),
        )
        ta.start()
        tb.start()
        ta.join()
        tb.join()

        tstats = registry.stats()
        alpha, beta = results["alpha"], results["beta"]
        noisy = {
            "quota_qps": quota_qps,
            "slo_ms": slo_ms,
            "alpha": {
                "ok": alpha["ok"], "errors": alpha["errors"],
                "shed": alpha["shed"],
                "shed_reasons": tstats["alpha"]["shed"],
                "admitted": tstats["alpha"]["admitted"],
            },
            "beta": {
                "ok": beta["ok"], "errors": beta["errors"],
                "shed": beta["shed"], "p99_ms": beta["p99Ms"],
                "slo_violations": tstats["beta"]["slo_violations"],
            },
            "gate_pass": bool(
                alpha["shed"] > 0
                and tstats["alpha"]["shed"]["quota"] > 0
                and alpha["errors"] == 0
                and beta["errors"] == 0
                and beta["shed"] == 0
                and (beta["p99Ms"] or 0.0) <= slo_ms
            ),
        }
    finally:
        if qs is not None:
            qs.stop()
        store_mod.set_storage(None)
        close_db(os.path.join(tmp, "events.sqlite"))
        if old_basedir is None:
            os.environ.pop("PIO_FS_BASEDIR", None)
        else:
            os.environ["PIO_FS_BASEDIR"] = old_basedir
        shutil.rmtree(tmp, ignore_errors=True)
    out["noisy_neighbor"] = noisy

    # -- composed pipeline vs single-stage exact --------------------------
    n_items = int(os.environ.get("BENCH_TENANT_ITEMS", 32768))
    rank = int(os.environ.get("BENCH_TENANT_RANK", 16))
    n_queries = int(os.environ.get("BENCH_TENANT_QUERIES", 300))
    n_users = 64
    nlist = 64
    rng = np.random.default_rng(23)
    # clustered catalog (same regime as the IVF gate): retrieval prunes
    # structure, and real item-factor matrices have it
    centers = (rng.normal(size=(nlist, rank)) * 4.0).astype(np.float32)
    item_cluster = rng.integers(0, nlist, size=n_items)
    V = (
        centers[item_cluster] + rng.normal(size=(n_items, rank)) * 0.25
    ).astype(np.float32)
    u_cluster = rng.integers(0, nlist, size=n_users)
    U = (
        centers[u_cluster] + rng.normal(size=(n_users, rank)) * 0.25
    ).astype(np.float32)
    model = ALSModel(
        user_factors=U,
        item_factors=V,
        user_map=BiMap({f"u{i}": i for i in range(n_users)}),
        item_map=BiMap({f"i{i}": i for i in range(n_items)}),
        ivf_index=ivf_mod.build_index(V, nlist),
    )
    scorer = ALSScorer(ctx, model)  # bench catalog < HOST_THRESHOLD: host path
    config = PipelineConfig(
        name="bench-two-stage",
        stages=(
            StageSpec("retrieve", "retrieval", 0.4,
                      params=(("candidates", 512),)),
            StageSpec("rank", "ranking", 0.6),
        ),
    )
    pipe = build_recommendation_stages(
        config, types.SimpleNamespace(_scorer=lambda m: scorer), model,
    )
    if pipe is None:
        raise RuntimeError("pipeline failed to bind the bench model")

    def drive_exact(i: int) -> None:
        scorer.recommend(i % n_users, 10)

    def drive_pipeline(i: int) -> None:
        pred, meta = pipe.run_pipeline(Query(user=f"u{i % n_users}", num=10))
        if meta.get("degraded"):
            raise RuntimeError("pipeline degraded with no deadline set")

    def timed(fn) -> tuple:
        for i in range(20):  # warm caches / lazy allocations
            fn(i)
        lats = []
        t0 = time.perf_counter()
        for i in range(n_queries):
            t1 = time.perf_counter()
            fn(i)
            lats.append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        lats.sort()
        p99 = lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3
        return n_queries / total, p99

    exact_qps, exact_p99 = timed(drive_exact)
    pipe_qps, pipe_p99 = timed(drive_pipeline)
    out["pipeline"] = {
        "n_items": n_items,
        "rank": rank,
        "queries": n_queries,
        "fingerprint": config.fingerprint,
        "exact_qps": round(exact_qps, 1),
        "exact_scores_per_s": round(exact_qps * n_items, 1),
        "exact_p99_ms": round(exact_p99, 3),
        "pipeline_qps": round(pipe_qps, 1),
        "pipeline_scores_per_s": round(pipe_qps * n_items, 1),
        "pipeline_p99_ms": round(pipe_p99, 3),
        "speedup": round(pipe_qps / exact_qps, 3),
        "stage_stats": pipe.stats()["stages"],
        "gate_pass": bool(
            pipe_qps > exact_qps and pipe_p99 <= 1.5 * exact_p99
        ),
    }
    out["gate_pass"] = bool(
        out["noisy_neighbor"]["gate_pass"] and out["pipeline"]["gate_pass"]
    )
    return out


def main() -> None:
    # BENCH_PLATFORM=cpu is the one explicit way onto the CPU (local
    # iteration at a shrunken workload).  Without it the bench needs an
    # accelerator: no chip is a non-zero exit, never a CPU number.
    forced_cpu = os.environ.get("BENCH_PLATFORM") == "cpu"
    if forced_cpu:
        print(
            "INFO: CPU requested via BENCH_PLATFORM; benchmarking on CPU "
            "(vs_baseline will be null)",
            file=sys.stderr,
        )
        os.environ["JAX_PLATFORMS"] = "cpu"
        # CPU cannot chew 25M ratings in reasonable time; shrink unless set
        os.environ.setdefault("BENCH_RATINGS", "1000000")
        os.environ.setdefault("BENCH_ITERS", "3")
        os.environ.setdefault("BENCH_USERS", "50000")
        os.environ.setdefault("BENCH_ITEMS", "10000")
    import jax

    from predictionio_tpu.parallel.mesh import MeshContext

    if not forced_cpu and jax.devices()[0].platform == "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform cpu); set "
            "BENCH_PLATFORM=cpu to run the shrunken CPU workload on purpose"
        )
    fallback = forced_cpu

    # MovieLens-25M scale (the reference's largest workload config) with the
    # recommendation template's default rank/iterations (BASELINE.md)
    n_users = int(os.environ.get("BENCH_USERS", 162_000))
    n_items = int(os.environ.get("BENCH_ITEMS", 59_000))
    n_ratings = int(os.environ.get("BENCH_RATINGS", 25_000_000))
    rank = int(os.environ.get("BENCH_RANK", 10))
    iterations = int(os.environ.get("BENCH_ITERS", 20))
    # BENCH_DTYPE=bf16 benches the bf16 gather/all-gather path (f32 solve
    # accumulation either way); default stays f32
    dtype = os.environ.get("BENCH_DTYPE", "f32")
    dist = os.environ.get("BENCH_DIST", "both")
    if dist not in ("uniform", "zipf", "both"):
        raise SystemExit(f"BENCH_DIST must be uniform|zipf|both, got {dist!r}")
    # parsed ONCE: the benched layout and the recorded workload flag must
    # come from the same read (BENCH_REBALANCE=0 = the no-LPT cell)
    rebalance = os.environ.get("BENCH_REBALANCE", "1") != "0"

    ctx = MeshContext.create()
    n_chips = ctx.n_devices
    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind

    results: dict[str, float] = {}
    models: dict[str, object] = {}
    times: dict[str, float] = {}
    for d in ("uniform", "zipf") if dist == "both" else (dist,):
        inter = _make_interactions(d, n_users, n_items, n_ratings)
        results[d], models[d], times[d] = _timed_run(
            ctx, inter, rank, iterations, dtype, n_chips, rebalance=rebalance
        )
        print(
            f"INFO: {d} distribution: {results[d]:.1f} events/s/chip",
            file=sys.stderr,
        )

    primary_dist = "uniform" if "uniform" in results else dist
    value = results[primary_dist]
    on_tpu = platform == "tpu" and not fallback

    utilization = _utilization(
        n_ratings, n_users, n_items, rank, iterations, dtype,
        times[primary_dist], n_chips, device_kind,
    )
    if os.environ.get("BENCH_MEASURED", "1") != "0":
        # measured fields must never kill the artifact (tensorflow proto
        # parse, profiler trace — both environment-sensitive)
        try:
            inter_m = _make_interactions(
                primary_dist, n_users, n_items,
                min(n_ratings, int(os.environ.get("BENCH_MEASURED_RATINGS",
                                                  4_000_000))),
            )
            utilization.update(
                _measured_utilization(ctx, inter_m, rank, dtype, device_kind,
                                      rebalance=rebalance)
            )
        except Exception as e:
            print(f"WARNING: measured utilization failed: {e}",
                  file=sys.stderr)
            utilization["measured_error"] = str(e)
    print(f"INFO: utilization: {utilization}", file=sys.stderr)

    solver_ab = None
    if on_tpu and os.environ.get("BENCH_SOLVER_AB", "1") != "0":
        # on real hardware, also time the scatter-based segment solver at a
        # REDUCED workload (it is ~orders slower there — docs/perf_roofline
        # .md) so every TPU artifact carries the dense-vs-segment evidence
        import predictionio_tpu.models.als as als_mod

        ab_ratings = min(n_ratings, 2_000_000)
        ab_iters = 2
        try:
            inter_ab = _make_interactions(
                primary_dist, n_users, n_items, ab_ratings
            )
            results_ab = {}
            for solver in ("dense", "segment"):
                cfg = als_mod.ALSConfig(
                    rank=rank, iterations=1, compute_dtype=dtype,
                    solver=solver,
                )
                als_mod.train_als(ctx, inter_ab, cfg)  # compile
                t0 = time.perf_counter()
                als_mod.train_als(
                    ctx, inter_ab,
                    als_mod.ALSConfig(
                        rank=rank, iterations=ab_iters,
                        compute_dtype=dtype, solver=solver,
                    ),
                )
                dt = time.perf_counter() - t0
                results_ab[solver] = round(
                    ab_ratings * ab_iters / dt / n_chips, 1
                )
            solver_ab = {
                **results_ab,
                "speedup_dense_vs_segment": round(
                    results_ab["dense"] / results_ab["segment"], 2
                ),
                "workload_ratings": ab_ratings,
                "iterations": ab_iters,
            }
            print(f"INFO: solver A/B: {solver_ab}", file=sys.stderr)
        except Exception as e:  # the A/B must never kill the artifact
            print(f"WARNING: solver A/B failed: {e}", file=sys.stderr)
            solver_ab = {"error": str(e)}

    latency = None
    if os.environ.get("BENCH_SERVING", "1") != "0":
        # serving benches must never kill the artifact: the training number
        # above is already earned, so failures degrade to an error field
        try:
            scorer_lat = _scorer_latency(
                ctx, models[primary_dist], on_device=True if on_tpu else None
            )
        except Exception as e:
            print(f"WARNING: scorer latency bench failed: {e}", file=sys.stderr)
            scorer_lat = {"error": str(e)}
        print(f"INFO: scorer latency: {scorer_lat}", file=sys.stderr)
        try:
            http_lat = _http_latency(ctx, primary_dist, n_users, n_items)
        except Exception as e:
            print(f"WARNING: http latency bench failed: {e}", file=sys.stderr)
            http_lat = {"error": str(e)}
        print(f"INFO: http latency: {http_lat}", file=sys.stderr)
        latency = {"scorer": scorer_lat, "http": http_lat}
    ingest = None
    if os.environ.get("BENCH_INGEST", "1") != "0":
        try:
            ingest = _ingest_bench()
        except Exception as e:  # ingest bench must never kill the artifact
            print(f"WARNING: ingest bench failed: {e}", file=sys.stderr)
            ingest = {"error": str(e)}
        print(f"INFO: ingest: {ingest}", file=sys.stderr)
    durability = None
    if os.environ.get("BENCH_DURABILITY", "1") != "0":
        try:
            durability = _durability_bench()
        except Exception as e:  # durability bench must never kill the artifact
            print(f"WARNING: durability bench failed: {e}", file=sys.stderr)
            durability = {"error": str(e)}
        print(f"INFO: durability: {durability}", file=sys.stderr)
    observability = None
    if os.environ.get("BENCH_OBS", "1") != "0":
        try:
            observability = _observability_bench(ctx)
        except Exception as e:  # the obs gate must never kill the artifact
            print(f"WARNING: observability bench failed: {e}", file=sys.stderr)
            observability = {"error": str(e)}
        print(f"INFO: observability: {observability}", file=sys.stderr)
    kernel = None
    if os.environ.get("BENCH_KERNEL", "1") != "0":
        try:
            kernel = _kernel_bench(
                platform,
                int(os.environ.get("BENCH_KERNEL_ITEMS", n_items)),
                rank,
            )
        except Exception as e:  # the kernel A/B must never kill the artifact
            print(f"WARNING: kernel bench failed: {e}", file=sys.stderr)
            kernel = {"error": str(e)}
        print(f"INFO: kernel: {kernel}", file=sys.stderr)
    train_kernel = None
    if os.environ.get("BENCH_TRAIN_KERNEL", "1") != "0":
        try:
            train_kernel = _train_kernel_bench(
                ctx, platform, n_users, n_items, n_ratings, rank,
            )
        except Exception as e:  # the train A/B must never kill the artifact
            print(f"WARNING: train-kernel bench failed: {e}", file=sys.stderr)
            train_kernel = {"error": str(e)}
        print(f"INFO: train_kernel: {train_kernel}", file=sys.stderr)
    fleet = None
    if os.environ.get("BENCH_FLEET", "1") != "0":
        try:
            fleet = _fleet_bench(ctx)
        except Exception as e:  # the fleet bench must never kill the artifact
            print(f"WARNING: fleet bench failed: {e}", file=sys.stderr)
            fleet = {"error": str(e)}
        print(f"INFO: fleet: {fleet}", file=sys.stderr)
    elastic = None
    if os.environ.get("BENCH_ELASTIC", "1") != "0":
        try:
            elastic = _elastic_bench(ctx)
        except Exception as e:  # the elastic bench must never kill the artifact
            print(f"WARNING: elastic bench failed: {e}", file=sys.stderr)
            elastic = {"error": str(e)}
        print(f"INFO: elastic: {elastic}", file=sys.stderr)
    freshness = None
    if os.environ.get("BENCH_FRESHNESS", "1") != "0":
        try:
            freshness = _freshness_bench(ctx)
        except Exception as e:  # the freshness bench must never kill the artifact
            print(f"WARNING: freshness bench failed: {e}", file=sys.stderr)
            freshness = {"error": str(e)}
        print(f"INFO: freshness: {freshness}", file=sys.stderr)
    sharded = None
    if os.environ.get("BENCH_SHARDED", "1") != "0":
        try:
            sharded = _sharded_serving_bench(ctx)
        except Exception as e:  # the sharding bench must never kill the artifact
            print(f"WARNING: sharded serving bench failed: {e}",
                  file=sys.stderr)
            sharded = {"error": str(e)}
        print(f"INFO: sharded_serving: {sharded}", file=sys.stderr)
    pod = None
    if os.environ.get("BENCH_POD", "1") != "0":
        try:
            pod = _pod_serving_bench()
        except Exception as e:  # the pod bench must never kill the artifact
            print(f"WARNING: pod serving bench failed: {e}", file=sys.stderr)
            pod = {"error": str(e)}
        print(f"INFO: pod_serving: {pod}", file=sys.stderr)
    retrieval = None
    if os.environ.get("BENCH_RETRIEVAL", "1") != "0":
        try:
            retrieval = _retrieval_bench(ctx, platform)
        except Exception as e:  # the IVF gate must never kill the artifact
            print(f"WARNING: retrieval bench failed: {e}", file=sys.stderr)
            retrieval = {"error": str(e)}
        print(f"INFO: retrieval: {retrieval}", file=sys.stderr)
    tenant = None
    if os.environ.get("BENCH_TENANT", "1") != "0":
        try:
            tenant = _tenant_bench(ctx)
        except Exception as e:  # the tenancy gate must never kill the artifact
            print(f"WARNING: tenant bench failed: {e}", file=sys.stderr)
            tenant = {"error": str(e)}
        print(f"INFO: tenant: {tenant}", file=sys.stderr)
    canary = None
    if os.environ.get("BENCH_CANARY", "1") != "0":
        try:
            canary = _canary_bench(ctx)
        except Exception as e:  # the canary gate must never kill the artifact
            print(f"WARNING: canary bench failed: {e}", file=sys.stderr)
            canary = {"error": str(e)}
        print(f"INFO: canary: {canary}", file=sys.stderr)
    record = {
        "metric": "als_train_events_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "events/s/chip",
        "vs_baseline": (
            round(value / NORTH_STAR_EVENTS_PER_SEC_PER_CHIP, 4) if on_tpu else None
        ),
        "platform": platform,
        "fallback": fallback,
        "n_devices": n_chips,
        "workload": {
            "users": n_users,
            "items": n_items,
            "ratings": n_ratings,
            "rank": rank,
            "iterations": iterations,
            "dtype": dtype,
            "distribution": primary_dist,
            "rebalance": rebalance,
        },
    }
    record["utilization"] = utilization
    record["solver"] = os.environ.get("PIO_ALS_SOLVER", "dense")
    if solver_ab is not None:
        record["solver_ab"] = solver_ab
    if latency is not None:
        record["predict_latency_ms"] = latency
        http_res = (latency.get("http") or {}).get("resilience")
        if http_res is not None:
            record["resilience"] = http_res
    if ingest is not None:
        record["ingest"] = ingest
    if durability is not None:
        record["durability"] = durability
    if observability is not None:
        record["observability"] = observability
    if kernel is not None:
        record["kernel"] = kernel
    if train_kernel is not None:
        record["train_kernel"] = train_kernel
    if fleet is not None:
        record["fleet"] = fleet
    if elastic is not None:
        record["elastic"] = elastic
    if freshness is not None:
        record["freshness"] = freshness
    if sharded is not None or pod is not None:
        record["multichip"] = {}
        if sharded is not None:
            record["multichip"]["sharded_serving"] = sharded
        if pod is not None:
            record["multichip"]["pod_serving"] = pod
    if retrieval is not None:
        record["retrieval"] = retrieval
    if tenant is not None:
        record["tenant"] = tenant
    if canary is not None:
        record["canary"] = canary
    if "zipf" in results and primary_dist != "zipf":
        record["zipf"] = {
            "value": round(results["zipf"], 1),
            "ratio_vs_uniform": round(results["zipf"] / value, 4),
        }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
