#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the ALS recommendation engine's normal path once, in ONE process,
through the entry points the CLI verbs call, at MovieLens-25M width
(162,000 users x 59,000 items, rank 10, f32, default solver):

    seeded EventBatch -> storage.get_p_events().write (parquet event store)
    -> core.workflow.run_train (template DataSource -> train_als)
    -> sealed publish -> QueryServer(batching=True) on a local port
    -> POST /queries.json over HTTP -> GET /, /readyz, /metrics

and, before that, lowers every Pallas kernel ``auto`` dispatch can select on
a TPU through Mosaic and runs it once against its XLA reference.

What comes out is checked against plain NumPy float64 (see ``HALF_STEP_TOL``
and ``SCORE_TOL``).  Catalog and rank are never cut; the number of ratings
is, and the cut is printed as ``reduced``.  The run's facts go out as one
``summary: {...}`` line (on a TPU also to ``chiprun_out/chip_smoke.json``); the
LAST line of standard output is the result, one JSON object with exactly
these keys: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``, the device as JAX reports it.

Exit codes: 0 every phase and check passed on a TPU; 1 a phase or check
failed; 2 the repo is not importable from here; 3 JAX found no TPU.
``--preset tiny`` is for debugging the command under ``JAX_PLATFORMS=cpu``:
it exits 0 when its phases pass but says ``"ok": false`` and
``platform: cpu`` — it is never a pass for the chip.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import json
import os
import shutil
import sys
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

PRESETS = {
    # MovieLens-25M shape (BASELINE.json).  ``ratings`` is the cut: the
    # event store's bulk write builds one Python Event per row (~20 us
    # each), so 25 M rows alone would take most of the 1200 s the chip
    # check allows; 5 M keeps every user and item rated and the load under
    # ~2 minutes.
    "full": dict(
        users=162_000, items=59_000, rank=10, ratings=5_000_000,
        source_ratings=25_000_000, iterations=2, queries=48, num=20,
        check_items=32, train_kernel_opp=12_288,
        train_kernel_buckets=((64, 128), (16, 1024)),
        score_rungs=(1, 8, 16, 32, 64), top_k=100, flash_t=512,
    ),
    "tiny": dict(
        users=300, items=200, rank=10, ratings=6_000, source_ratings=6_000,
        iterations=2, queries=12, num=5, check_items=8,
        train_kernel_opp=64, train_kernel_buckets=((8, 8),),
        score_rungs=(1, 8), top_k=20, flash_t=128,
    ),
}

# -- tolerances, with their reasons ------------------------------------------
# Both checks compare f32 results with a float64 reference and must keep
# telling f32 from a lower precision: one bf16 MXU pass (a TPU's default for
# f32 operands) carries 2^-9 ~ 2e-3 relative error per product.
#
# Half-step: x solves A x = b with A = sum u u^T + (reg*n + 1e-6) I, a
# rank-10 SPD system built and factored in f32.  A backward-stable f32
# solve errs by a modest multiple of 2^-24 * cond(A) * |x| (cond computed
# here in float64, per item: a cold item with one rating has cond ~ 1e3, a
# popular one ~ 1e1), so the bound scales with cond(A) instead of guessing
# one number for both.  One bf16 pass would put 2^-9 * cond(A) * |x| there:
# the factor below leaves f32 its headroom and still sits ~500x under that.
HALF_STEP_TOL = 64 * 2.0 ** -24
# Scores: a 10-term f32 dot product errs by a few 2^-24 of |u|*|v|.  1e-5 of
# that scale is ~20x what f32 needs and 200x below one bf16 pass.
SCORE_TOL = 1e-5


def log(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class Phases:
    """Seconds per phase, with compile seconds (JAX's own monitoring events:
    trace + lowering + backend compile, cache retrieval included) apart from
    the rest, and the persistent cache's hits and misses."""

    COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.records: dict[str, dict] = {}
        self.failed: list[str] = []
        self._compile_s = 0.0
        self._cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, duration, **_):
        if event in self.COMPILE_EVENTS:
            self._compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self._cache["misses"] += 1

    def run(self, name: str, fn, needs=()):
        """Run one phase; a failure is recorded with its traceback and the
        run goes on to the summary.  A phase whose ``needs`` did not pass
        is not run, and counts as failed."""
        missing = [n for n in needs if not self.records.get(n, {}).get("ok")]
        if missing:
            self.records[name] = {
                "ok": False, "skipped": f"needs {missing}", "seconds": 0.0,
                "compile_seconds": 0.0, "run_seconds": 0.0,
                "cache_hits": 0, "cache_misses": 0,
            }
            self.failed.append(name)
            log(f"phase {name}: SKIPPED, needs {missing}")
            return None
        log(f"phase {name} ...")
        c0, k0, t0 = self._compile_s, dict(self._cache), time.perf_counter()
        rec: dict = {"ok": False}
        out = None
        try:
            out = fn()
            rec["ok"] = True
        except Exception as e:  # boundary: record, report, fail the run
            rec["error"] = f"{type(e).__name__}: {e}"[:4000]
            traceback.print_exc()
            self.failed.append(name)
        wall = time.perf_counter() - t0
        rec["seconds"] = round(wall, 3)
        rec["compile_seconds"] = round(self._compile_s - c0, 3)
        rec["run_seconds"] = round(max(0.0, wall - (self._compile_s - c0)), 3)
        rec["cache_hits"] = self._cache["hits"] - k0["hits"]
        rec["cache_misses"] = self._cache["misses"] - k0["misses"]
        self.records[name] = rec
        log(f"phase {name}: {'ok' if rec['ok'] else 'FAILED'} in "
            f"{rec['seconds']} s (compile {rec['compile_seconds']} s)")
        return out


# -- float64 references -------------------------------------------------------


def check_topk(U64, V64, users, idx, vals, what: str) -> dict:
    """Returned scores equal U[u].V[i] and no unreturned item beats the
    k-th, for every row — against float64, to ``SCORE_TOL`` of |u|*max|v|."""
    import numpy as np

    vmax = float(np.linalg.norm(V64, axis=1).max())
    worst = 0.0
    for r, u in enumerate(users):
        s = V64 @ U64[u]
        tol = SCORE_TOL * float(np.linalg.norm(U64[u])) * vmax
        got_i = np.asarray(idx[r], np.int64)
        got_v = np.asarray(vals[r], np.float64)
        if len(set(got_i.tolist())) != len(got_i):
            raise AssertionError(f"{what}: row {r} returns an item twice")
        err = float(np.abs(got_v - s[got_i]).max())
        rest = s.copy()
        rest[got_i] = -np.inf
        beat = float(rest.max() - s[got_i].min())
        order = float(np.max(np.diff(got_v), initial=-np.inf))
        worst = max(worst, err / tol, beat / tol, order / tol)
        if err > tol or beat > tol or order > tol:
            raise AssertionError(
                f"{what}: row {r} (user {u}): score error {err:.3e}, "
                f"unreturned item beats the k-th by {beat:.3e}, order "
                f"violation {order:.3e}; tolerance {tol:.3e}"
            )
    return {"rows": len(users), "worst_over_tolerance": round(worst, 4)}


def check_half_step(U64, item_rows, ratings_of, reg: float) -> dict:
    """Each sampled item row equals the normal-equation solve from the final
    user factors and that item's ratings — one exact half-step, float64."""
    import numpy as np

    rank = U64.shape[1]
    worst = 0.0
    for j, row in item_rows.items():
        users, r = ratings_of(j)
        Uu = U64[users]
        A = Uu.T @ Uu + (reg * len(users) + 1e-6) * np.eye(rank)
        x = np.linalg.solve(A, Uu.T @ r)
        err = float(np.abs(np.asarray(row, np.float64) - x).max())
        tol = HALF_STEP_TOL * float(np.linalg.cond(A)) * float(np.abs(x).max())
        worst = max(worst, err / tol)
        if err > tol:
            raise AssertionError(
                f"half-step: item {j} ({len(users)} ratings, cond "
                f"{np.linalg.cond(A):.1e}) differs from the float64 solve "
                f"by {err:.3e}; tolerance {tol:.3e}"
            )
    return {"items": len(item_rows), "worst_over_tolerance": round(worst, 4)}


# -- phases -------------------------------------------------------------------


def kernels_phase(cfg: dict, seed: int, on_tpu: bool, ctx) -> dict:
    """Every Pallas kernel ``auto`` can select on a TPU, run once at the
    smoke's shapes against its XLA reference (and float64)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops import flash_attention as fa
    from predictionio_tpu.ops import score_kernel, train_kernel
    from predictionio_tpu.ops.topk import gather_score_topk
    from predictionio_tpu.parallel.ring import (
        full_attention, ring_flash_attention)
    from predictionio_tpu.parallel.ulysses import ulysses_attention

    rng = np.random.default_rng(seed)
    out: dict = {}

    # score kernel at every bucket rung
    n_u, n_i, rank, k = cfg["users"], cfg["items"], cfg["rank"], cfg["top_k"]
    scale = 1.0 / np.sqrt(rank)
    U = (rng.standard_normal((n_u, rank)) * scale).astype(np.float32)
    V = (rng.standard_normal((n_i, rank)) * scale).astype(np.float32)
    n_pad = score_kernel.pad_block_items(n_i)
    Vp = jnp.asarray(np.pad(V, ((0, n_pad - n_i), (0, 0))))
    pad_mask = jnp.asarray(np.arange(n_pad) >= n_i)
    Ud = jnp.asarray(U)
    U64, V64 = U.astype(np.float64), V.astype(np.float64)
    rungs = {}
    for b in cfg["score_rungs"]:
        users = rng.integers(0, n_u, b).astype(np.int32)
        res = {}
        for backend in ("fused", "reference"):
            fn = jax.jit(
                lambda U_, V_, i_, m_, be=backend: gather_score_topk(
                    U_, V_, i_, k, item_mask=m_, backend=be
                )
            )
            vals, idx = jax.block_until_ready(
                fn(Ud, Vp, jnp.asarray(users), pad_mask)
            )
            res[backend] = (np.asarray(idx), np.asarray(vals))
            check_topk(U64, V64, users, *res[backend],
                       what=f"score kernel rung {b} ({backend})")
        rungs[str(b)] = {
            "fused_vs_reference_same_items": bool(
                np.array_equal(res["fused"][0], res["reference"][0])
            ),
            "max_abs_diff": float(
                np.abs(res["fused"][1] - res["reference"][1]).max()
            ),
        }
    out["score_topk"] = {"rungs": rungs, "k": k, "items": n_i, "rank": rank}

    # train kernel at the widest side the static rule admits, per bucket
    n_opp = cfg["train_kernel_opp"]
    if not train_kernel.fits_vmem(n_opp, rank, "f32"):
        raise AssertionError(f"train kernel probe width {n_opp} is refused")
    Vo = (rng.standard_normal((n_opp, rank)) * scale).astype(np.float32)
    buckets = {}
    for n_b, D in cfg["train_kernel_buckets"]:
        idx = rng.integers(0, n_opp, (n_b, D)).astype(np.int32)
        rat = rng.integers(1, 6, (n_b, D)).astype(np.float32)
        msk = (rng.random((n_b, D)) < 0.8).astype(np.float32)
        A, bv, cnt = jax.block_until_ready(jax.jit(
            lambda i, r, m, V_: train_kernel.fused_train_normal_eq(i, r, m, V_)
        )(idx, rat, msk, Vo))
        W = Vo.astype(np.float64)[idx] * msk[:, :, None]
        A64 = np.einsum("edk,edl->ekl", W, W)
        b64 = np.einsum("edk,ed->ek", W, rat.astype(np.float64))
        tol = 1e-5 * float(np.abs(A64).max())  # f32 sum of <= D products
        errs = (float(np.abs(np.asarray(A) - A64).max()),
                float(np.abs(np.asarray(bv) - b64).max()))
        if max(errs) > tol * 5 or not np.array_equal(
            np.asarray(cnt), msk.sum(1)
        ):
            raise AssertionError(
                f"train kernel bucket ({n_b},{D}): errors {errs} vs float64, "
                f"tolerance {tol * 5:.3e}"
            )
        buckets[f"{n_b}x{D}"] = {"max_abs_err_A": errs[0],
                                 "max_abs_err_b": errs[1]}
    g_idx = rng.integers(0, n_opp, 4096 if on_tpu else 64).astype(np.int32)
    rows = jax.block_until_ready(jax.jit(
        lambda V_, i: train_kernel.fused_gather_rows(V_, i)
    )(Vo, g_idx))
    if not np.array_equal(np.asarray(rows), Vo[g_idx]):
        raise AssertionError("train gather kernel: rows differ from V[idx]")
    out["train_contract"] = {"n_opp": n_opp, "buckets": buckets}
    out["train_gather_rows"] = {"n_opp": n_opp, "rows": len(g_idx)}

    # flash attention forward and backward (the sequence model's head shape)
    # — alone, and under the two sequence-parallel wrappers over every
    # visible device (one chip: the wrappers compile; four: their
    # collectives run too).  Ulysses shards heads, so there is one per device.
    T, n_dev = cfg["flash_t"], ctx.axis_size("data")
    q, kk, v = (
        jnp.asarray(rng.standard_normal(
            (2, max(2, n_dev), T, 16)).astype(np.float32))
        for _ in range(3)
    )

    def loss(attn, q_, k_, v_):
        return (attn(q_, k_, v_, causal=True) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        o_ref = full_attention(q, kk, v, causal=True)
        g_ref = jax.grad(
            lambda *a: loss(full_attention, *a), argnums=(0, 1, 2))(q, kk, v)
    for name, attn in (
        ("flash_attention", fa.flash_attention),
        ("ring_flash_attention", functools.partial(ring_flash_attention, ctx)),
        ("ulysses_attention",
         functools.partial(ulysses_attention, ctx, use_flash=True)),
    ):
        o = attn(q, kk, v, causal=True)
        g = jax.grad(lambda *a: loss(attn, *a), argnums=(0, 1, 2))(q, kk, v)
        fwd = float(jnp.abs(o - o_ref).max() / jnp.abs(o_ref).max())
        bwd = max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
                  for a, b in zip(g, g_ref))
        # the kernel states no precision — like the dense attention it
        # replaces it takes the platform's default for its f32 matmuls — so
        # the bound is bf16-pass sized (2^-9 per product, through a
        # softmax), not an f32 one
        if not (fwd < 3e-2 and bwd < 3e-2):
            raise AssertionError(f"{name}: fwd {fwd:.3e} bwd {bwd:.3e}")
        out[name] = {"T": T, "heads": q.shape[1], "head_dim": 16,
                     "devices": 1 if name == "flash_attention" else n_dev,
                     "max_rel_diff_fwd": fwd, "max_rel_diff_bwd": bwd}
    return out


def make_events(cfg: dict, seed: int):
    """Seeded rating events: every user and every item appears at least once
    (the catalog is never cut), the rest follows the bench's Zipf-Mandelbrot
    popularity; integer ratings 1..5."""
    import numpy as np

    from predictionio_tpu.data.batch import EventBatch
    from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights

    rng = np.random.default_rng(seed)
    n_u, n_i, n = cfg["users"], cfg["items"], cfg["ratings"]
    cover = max(n_u, n_i)
    if n < cover:
        raise ValueError(f"{n} ratings cannot cover {cover} entities")
    users = np.empty(n, np.int64)
    items = np.empty(n, np.int64)
    users[:cover] = np.arange(cover) % n_u
    items[:cover] = np.arange(cover) % n_i
    users[cover:] = rng.choice(
        n_u, n - cover, p=zipf_mandelbrot_weights(n_u, s=0.7, q=50.0))
    items[cover:] = rng.choice(
        n_i, n - cover, p=zipf_mandelbrot_weights(n_i, s=1.1, q=50.0))
    ratings = rng.integers(1, 6, n)
    by_value = {r: {"rating": float(r)} for r in range(1, 6)}
    u_names = np.array([f"u{i}" for i in range(n_u)], object)
    i_names = np.array([f"i{i}" for i in range(n_i)], object)
    batch = EventBatch(
        event=np.full(n, "rate", object),
        entity_type=np.full(n, "user", object),
        entity_id=u_names[users],
        target_entity_type=np.full(n, "item", object),
        target_entity_id=i_names[items],
        event_time=np.full(n, 1.7e9, np.float64),
        properties=[by_value[int(r)] for r in ratings],
    )
    return batch, users, items, ratings.astype(np.float64)


def http_json(url: str, payload=None, timeout: float = 60.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method="GET" if payload is None else "POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read().decode()
    return json.loads(body) if body.lstrip().startswith(("{", "[")) else body


def metric_value(text: str, name: str) -> float:
    """Sum of one Prometheus family's samples (labels ignored)."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise AssertionError(f"/metrics has no {name}")
    return total


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--preset", choices=sorted(PRESETS), default="full")
    ap.add_argument("--ratings", type=int, default=None,
                    help="override the preset's number of rating events")
    ap.add_argument("--workdir", default=os.path.join(HERE, ".chip_smoke"),
                    help="event store, models and pio base dir (wiped)")
    args = ap.parse_args()
    cfg = dict(PRESETS[args.preset])
    if args.ratings is not None:
        cfg["ratings"] = args.ratings

    try:
        sys.path.insert(0, HERE)
        import predictionio_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repo is not importable from {HERE}: {e}",
              file=sys.stderr)
        return 2

    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    found = (f"platform: {device['platform']}  device_kind: {device['kind']}  "
             f"count: {device['count']}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not (args.preset == "tiny"
                           and device["platform"] == "cpu"):
        # nothing on standard output: a refusal prints no result
        print(f"chip_smoke: JAX found no TPU ({found}); only `--preset tiny` "
              "under JAX_PLATFORMS=cpu may run elsewhere, and it is never a "
              "pass", file=sys.stderr)
        return 3
    print(found, flush=True)

    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    os.environ["PIO_FS_BASEDIR"] = os.path.join(args.workdir, "pio_store")
    state: dict = {}
    try:
        return drive(args, cfg, dev, device, state)
    finally:  # whatever happened: no server left running, no store left behind
        if state.get("qs") is not None:
            state["qs"].stop()
        shutil.rmtree(args.workdir, ignore_errors=True)


def drive(args, cfg: dict, dev, device: dict, state: dict) -> int:
    """Every phase, then the ``summary:`` line, then the result line."""
    import numpy as np

    from predictionio_tpu import native
    from predictionio_tpu.core.workflow import prepare_deploy, run_train
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.data.storage.registry import Storage
    from predictionio_tpu.ops import pallas_mode, train_kernel
    from predictionio_tpu.parallel import mesh as mesh_mod
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.templates.recommendation import RecommendationEngine

    on_tpu = device["platform"] == "tpu"
    phases = Phases()
    ctx = mesh_mod.MeshContext.create()  # also places the compile cache
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or mesh_mod.COMPILE_CACHE_DIR)
    cache_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    facts: dict = {}

    facts["kernels"] = phases.run(
        "kernels", lambda: kernels_phase(cfg, args.seed, on_tpu, ctx))

    def store_phase():
        env = {
            "PIO_STORAGE_SOURCES_META_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_META_PATH":
                os.path.join(args.workdir, "meta.db"),
            "PIO_STORAGE_SOURCES_EVENTS_TYPE": "parquet",
            "PIO_STORAGE_SOURCES_EVENTS_PATH":
                os.path.join(args.workdir, "events"),
            "PIO_STORAGE_SOURCES_MODELS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_MODELS_PATH":
                os.path.join(args.workdir, "models"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EVENTS",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MODELS",
        }
        storage = Storage(env=env)
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "smokeapp"))
        storage.get_l_events().init(app_id)
        batch, users, items, ratings = make_events(cfg, args.seed)
        storage.get_p_events().write(batch, app_id)
        state.update(storage=storage, users=users, items=items,
                     ratings=ratings)
        return {"events_written": len(users), "native": native.status()}

    facts["store"] = phases.run("store", store_phase)

    variant = {
        "engineFactory":
            "predictionio_tpu.templates.recommendation.RecommendationEngine",
        "datasource": {"params": {"appName": "smokeapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": cfg["rank"], "numIterations": cfg["iterations"],
            "lambda": 0.01, "seed": args.seed,
        }}],
    }
    engine = RecommendationEngine.apply()

    def train_phase():
        instance_id = run_train(
            engine, engine.params_from_variant(variant),
            engine_factory=variant["engineFactory"],
            storage=state["storage"], ctx=ctx,
            engine_id="smoke", engine_version="1", engine_variant="default",
        )
        state["instance_id"] = instance_id
        tk = train_kernel.stats()
        return {
            "instance_id": instance_id,
            "backend_u_solve": tk.get("backend_u_solve"),
            "backend_v_solve": tk.get("backend_v_solve"),
            "compute_dtype": tk.get("compute_dtype"),
            "refused_fused_u_solve": train_kernel.refusal(
                cfg["items"], cfg["rank"], "f32"),
            "refused_fused_v_solve": train_kernel.refusal(
                cfg["users"], cfg["rank"], "f32"),
        }

    facts["train"] = phases.run("train", train_phase, needs=("store",))

    def published_phase():
        # the PUBLISHED factors, read back through the deploy entry point
        storage = state["storage"]
        instance = storage.get_meta_data_engine_instances().get(
            state["instance_id"])
        _, _, _, models = prepare_deploy(engine, instance, storage, ctx)
        m = models[0]
        if (m.user_factors.shape != (cfg["users"], cfg["rank"])
                or m.item_factors.shape != (cfg["items"], cfg["rank"])):
            raise AssertionError(
                f"factor shapes {m.user_factors.shape} / "
                f"{m.item_factors.shape} are not the configured width")
        bad = (int((~np.isfinite(m.user_factors).all(axis=1)).sum()),
               int((~np.isfinite(m.item_factors).all(axis=1)).sum()))
        if any(bad):
            raise AssertionError(
                f"non-finite factors: {bad[0]} user rows, {bad[1]} item rows")
        state.update(
            model=m, U64=m.user_factors.astype(np.float64),
            V64=m.item_factors.astype(np.float64),
            u_of=np.array([m.user_map[f"u{i}"] for i in range(cfg["users"])]),
            i_of=np.array([m.item_map[f"i{i}"] for i in range(cfg["items"])]),
        )
        plan = getattr(m, "sharding_plan", None)
        return {"sharding_plan_shards": plan.n_shards if plan else None}

    facts["published"] = phases.run(
        "published", published_phase, needs=("train",))

    def half_step_phase():
        m = state["model"]
        order = np.argsort(state["items"], kind="stable")
        starts = np.searchsorted(state["items"][order],
                                 np.arange(cfg["items"] + 1))
        rng = np.random.default_rng(args.seed + 1)
        # the most-rated item plus a seeded sample
        counts = np.diff(starts)
        sample = {int(np.argmax(counts))} | set(
            rng.choice(cfg["items"], cfg["check_items"] - 1,
                       replace=False).tolist())

        def ratings_of(gen_item):
            rows = order[starts[gen_item]:starts[gen_item + 1]]
            return state["u_of"][state["users"][rows]], state["ratings"][rows]

        rows = {g: m.item_factors[state["i_of"][g]] for g in sample}
        res = check_half_step(state["U64"], rows, ratings_of, reg=0.01)
        res["max_ratings_per_item"] = int(counts.max())
        return res

    facts["half_step_check"] = phases.run(
        "half_step_check", half_step_phase, needs=("published",))

    def deploy_phase():
        qs = QueryServer(
            engine, storage=state["storage"], ctx=ctx, engine_id="smoke",
            engine_version="1", engine_variant="default", batching=True,
        )
        state["qs"] = qs
        state["base"] = f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
        root = http_json(state["base"] + "/")
        fp = (root.get("fastpath") or [None])[0]
        if fp is None:
            raise AssertionError("deployed without a fast path")
        state["fp_before"] = fp
        return {
            "score_backend_per_rung": {
                str(b): fp["kernel"]["backend"] for b in fp["buckets"]},
            "serving_backend": fp["serving_backend"],
            "compile_count": fp["compile_count"],
            "warmup_executions": fp["kernel"]["warmup_executions"],
        }

    facts["deploy"] = phases.run("deploy", deploy_phase, needs=("train",))

    def queries_phase():
        base, U64, V64 = state["base"], state["U64"], state["V64"]
        rng = np.random.default_rng(args.seed + 2)
        gen_users = rng.choice(cfg["users"], cfg["queries"], replace=False)
        item_index = {
            name: j for j, name in state["model"].item_map.inverse.items()}

        def ask(g):
            return http_json(base + "/queries.json",
                             {"user": f"u{g}", "num": cfg["num"]})

        # a trickle first (rung 1, inline), then one burst (batches form)
        n_seq = cfg["queries"] // 3
        answers = [ask(g) for g in gen_users[:n_seq]]
        with concurrent.futures.ThreadPoolExecutor(32) as pool:
            answers += list(pool.map(ask, gen_users[n_seq:]))
        idx, vals = [], []
        for g, a in zip(gen_users, answers):
            scores = a.get("itemScores") or []
            if a.get("degraded") or len(scores) != cfg["num"]:
                raise AssertionError(f"user u{g}: bad answer {str(a)[:200]}")
            idx.append([item_index[s["item"]] for s in scores])
            vals.append([s["score"] for s in scores])
        res = check_topk(U64, V64, state["u_of"][gen_users], idx, vals,
                         what="/queries.json")
        res["http_requests"] = len(answers)
        return res

    facts["queries"] = phases.run(
        "queries", queries_phase, needs=("published", "deploy"))

    def readback_phase():
        base, before = state["base"], state["fp_before"]
        root = http_json(base + "/")
        ready = http_json(base + "/readyz")
        metrics = http_json(base + "/metrics")
        fp = root["fastpath"][0]
        counters = root["resilience"]["counters"]
        res = {
            "fastpathWarm": ready.get("fastpathWarm"),
            "engineInstanceId": ready.get("engineInstanceId"),
            "servingBackend": ready.get("servingBackend"),
            "reloadDegraded": ready.get("reloadDegraded"),
            "warmup_errors": counters.get("warmup_errors"),
            "degraded": counters.get("degraded"),
            "query_errors": counters.get("query_errors"),
            "compile_count_before": before["compile_count"],
            "compile_count_after": fp["compile_count"],
            "device_dispatches": fp["calls"] - before["calls"],
            "bucket_hits": fp["bucket_hits"],
            "metrics_fastpath_calls_total":
                metric_value(metrics, "pio_fastpath_calls_total"),
            "metrics_fastpath_compiles_total":
                metric_value(metrics, "pio_fastpath_compiles_total"),
            "metrics_device_dispatches_total":
                metric_value(metrics, "pio_device_dispatches_total"),
        }
        problems = [
            msg for bad, msg in (
                (res["fastpathWarm"] is not True, "fastpathWarm is not true"),
                (res["engineInstanceId"] != state["instance_id"],
                 "the served generation is not the trained one"),
                (res["reloadDegraded"], "reloadDegraded"),
                (res["warmup_errors"] != 0, "warmup_errors != 0"),
                (res["degraded"] != 0, "degraded != 0"),
                (res["query_errors"] != 0, "query_errors != 0"),
                (res["compile_count_after"] != res["compile_count_before"],
                 "a query compiled"),
                (res["device_dispatches"] <= 0, "no device dispatch"),
                (res["metrics_fastpath_calls_total"] != fp["calls"],
                 "/metrics and / disagree on dispatches"),
            ) if bad
        ]
        want_shards = int(os.environ.get("PIO_SHARD_COUNT", "0") or 0)
        if 1 < want_shards <= device["count"] and \
                res["servingBackend"] != "sharded":
            problems.append(
                f"PIO_SHARD_COUNT={want_shards} but serving backend is "
                f"{res['servingBackend']}")
        if problems:
            raise AssertionError("; ".join(problems) + f" — {res}")
        return res

    facts["readback"] = phases.run(
        "readback", readback_phase, needs=("queries",))
    if state.get("qs") is not None:
        state.pop("qs").stop()

    traces = pallas_mode.traces()
    if on_tpu and any(t["interpreted"] for t in traces.values()):
        phases.failed.append("no_interpreted_kernel")
    memory = []
    for d in dev:
        ms = d.memory_stats() or {}
        memory.append({"id": d.id, "bytes_in_use": ms.get("bytes_in_use"),
                       "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    cache_after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    phases_ok = not phases.failed
    summary = {
        "ok": phases_ok and on_tpu,
        "device": device,
        "preset": args.preset,
        "seed": args.seed,
        "phases_ok": phases_ok,
        "failed": phases.failed,
        "config": {"users": cfg["users"], "items": cfg["items"],
                   "rank": cfg["rank"], "ratings": cfg["ratings"],
                   "iterations": cfg["iterations"],
                   "compute_dtype": "f32", "solver": "dense"},
        "reduced": (
            {"ratings": {"source": cfg["source_ratings"],
                         "run": cfg["ratings"],
                         "why": "the event store's bulk write is per-event "
                                "Python; the full count would not fit the "
                                "chip check's time limit"}}
            if cfg["ratings"] < cfg["source_ratings"] else {}),
        "phases": phases.records,
        "facts": facts,
        "pallas_traces": traces,
        "compile_cache": {
            "dir": cache_dir,
            "placed_by": ("JAX_COMPILATION_CACHE_DIR"
                          if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          else "predictionio_tpu.parallel.mesh"),
            "entries_before": cache_before, "entries_after": cache_after},
        "compile_seconds_total": round(
            sum(p["compile_seconds"] for p in phases.records.values()), 3),
        "memory_stats": memory,
        "claim": None,
    }
    if not on_tpu:
        summary["note"] = "CPU preset: never a pass for the chip"
    if on_tpu:  # what a chip run brings back; a CPU rehearsal leaves no file
        out_dir = os.path.join(HERE, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(summary, f, indent=1)
    print("summary: " + json.dumps(summary), flush=True)
    # the result, last and alone: exactly these keys (the chip check's contract)
    print(json.dumps({"ok": summary["ok"], "device": device}), flush=True)
    return 0 if phases_ok else 1


if __name__ == "__main__":
    sys.exit(main())
