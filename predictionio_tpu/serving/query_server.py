"""Query server: low-latency REST serving of deployed engines.

Parity: ``core/.../workflow/CreateServer.scala:104-706``:

* ``POST /queries.json`` — parse query → ``serving.supplement`` → per-algorithm
  ``predict`` → ``serving.serve`` (the in-process hot loop,
  ``CreateServer.scala:484-634``).
* ``GET /`` — server info with request count / avg / last serving seconds
  (``:415-417,597-604``).
* ``GET|POST /reload`` — hot-swap to the latest COMPLETED instance without
  dropping queries (``:342-371,635-642``); models are re-placed on the mesh
  and the handle swapped atomically.
* ``POST /stop`` — undeploy (``commands/Engine.scala:245-268`` calls this).
* ``GET /plugins.json`` + outputblocker/outputsniffer plugin hooks
  (``EngineServerPlugin.scala:24-40``, ``CreateServer.scala:591-595,656-702``).
* feedback loop: when enabled, every prediction is POSTed back to the event
  server tagged with ``prId`` (``CreateServer.scala:527-589``).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import queue
import secrets
import threading
import time
import urllib.request
from typing import Any, Optional

from predictionio_tpu.common import faults as _faults
from predictionio_tpu.common.http import HttpService, Request, Response, json_response
from predictionio_tpu.common.resilience import (
    DEADLINE_HEADER,
    BreakerOpen,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    ErrorCounters,
    RateLimitedLogger,
    RetryPolicy,
    call_with_resilience,
    deadline_scope,
    parse_deadline_header,
)
from predictionio_tpu import obs
from predictionio_tpu.core import delta as _delta
from predictionio_tpu.core.engine import Engine
from predictionio_tpu.core import persistence
from predictionio_tpu.core.persistence import open_model_blob
from predictionio_tpu.core.workflow import (
    get_latest_completed_instance,
    prepare_deploy,
)
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import bridges as _bridges
from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import tracing as _tracing
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.pipeline import (
    build_pipeline_engine,
    pipeline_from_env,
)
from predictionio_tpu.serving.result_cache import (
    canonical_fingerprint,
    coalesce_from_env,
    entity_ids_from,
    result_cache_from_env,
)
from predictionio_tpu.serving.tenancy import (
    extract_access_key,
    tenants_from_env,
)
from predictionio_tpu.utils.profiling import LatencyHistogram

logger = logging.getLogger(__name__)


class EngineServerPlugin:
    """Parity: workflow/EngineServerPlugin.scala:24-40."""

    OUTPUT_BLOCKER = "outputblocker"
    OUTPUT_SNIFFER = "outputsniffer"

    name = "plugin"
    plugin_type = OUTPUT_SNIFFER

    def process(self, query: Any, prediction: Any, context: dict) -> Any:
        """Blockers return a (possibly rewritten) prediction; sniffers observe."""
        return prediction


# response-field plans: dataclasses.fields() re-derives the field tuple on
# every call; a deployed engine serves millions of instances of the SAME
# few result types, so the names are cached per class after the first walk
_FIELD_PLANS: dict[type, tuple[str, ...]] = {}


def _to_jsonable(obj: Any) -> Any:
    plan = _FIELD_PLANS.get(type(obj))
    if plan is not None:
        # None-valued fields are omitted, matching the reference's json4s
        # treatment of Option None (absent field, not null)
        return {
            k: _to_jsonable(v) for k in plan if (v := getattr(obj, k)) is not None
        }
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        plan = tuple(f.name for f in dataclasses.fields(obj))
        _FIELD_PLANS[type(obj)] = plan
        return {
            k: _to_jsonable(v) for k in plan if (v := getattr(obj, k)) is not None
        }
    if isinstance(obj, (list, tuple)):
        return [_to_jsonable(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _to_jsonable(v) for k, v in obj.items()}
    return obj


def bind_query(query_cls: Optional[type], data: dict) -> Any:
    """Lenient query binding (parity: JsonExtractor dual Gson/json4s path —
    unknown JSON fields are ignored, missing ones take defaults)."""
    if query_cls is None or not dataclasses.is_dataclass(query_cls):
        return data
    names = {f.name for f in dataclasses.fields(query_cls)}
    return query_cls(**{k: v for k, v in data.items() if k in names})


@dataclasses.dataclass
class _Deployed:
    instance_id: str
    algorithms: list
    serving: Any
    models: list
    start_time: float


def _batch_buckets(algorithms, default: tuple) -> tuple:
    """Where the batcher may cut a batch: the row ladder of the deployed
    algorithm's device programs if it states one (``batch_row_ladder``; a
    scorer that packs any number of rows into one program says
    "anywhere"), else ``default``, the bucketed scorer's.  Every algorithm
    of an engine runs every batch, so several must agree or ``default``
    stays."""
    ladders = {tuple(getattr(algo, "batch_row_ladder", None) or default)
               for algo in algorithms}
    return ladders.pop() if len(ladders) == 1 else tuple(default)


class QueryServer:
    def __init__(
        self,
        engine: Engine,
        storage: Optional[Storage] = None,
        ctx: Optional[MeshContext] = None,
        engine_id: str = "default",
        engine_version: str = "default",
        engine_variant: str = "default",
        feedback: bool = False,
        event_server_url: Optional[str] = None,
        access_key: Optional[str] = None,
        plugins: Optional[list[EngineServerPlugin]] = None,
        batching: bool = False,
        max_batch: int = 64,
        max_inflight: int = 256,
        shed_retry_after_s: float = 1.0,
        default_deadline_ms: Optional[float] = None,
        warm_fastpath: Optional[bool] = None,
        telemetry: bool = True,
        result_cache=None,
        coalesce: Optional[bool] = None,
        tenants=None,
        pipeline=None,
    ):
        self.engine = engine
        self.storage = storage or Storage.instance()
        self.ctx = ctx or MeshContext.create()
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant
        self.feedback = feedback
        self.event_server_url = event_server_url
        self.access_key = access_key
        self.plugins = list(plugins or [])
        self._deployed: Optional[_Deployed] = None
        self._lock = threading.Lock()
        # latency bookkeeping (parity: CreateServer.scala:415-417) plus a
        # full histogram (TPU-build observability, SURVEY.md §5)
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        self.latency = LatencyHistogram()
        self.service = HttpService("queryserver")
        # unified observability (obs/): /metrics + /trace/recent.json, and
        # the HTTP layer's request counter / latency / trace hooks
        self.telemetry = (
            obs.Telemetry("queryserver").install(self.service)
            if telemetry and obs.telemetry_enabled()
            else None
        )
        # feedback POSTs ride a bounded background queue, never the request
        # thread; when the event server can't keep up we drop (and count)
        # rather than let feedback add to serve latency
        self._feedback_queue: "queue.Queue[dict]" = queue.Queue(maxsize=256)
        self._feedback_dropped = 0
        self._feedback_worker: Optional[threading.Thread] = None
        # -- resilience layer (ISSUE 2): admission control, deadlines,
        # degraded fallback, counted + rate-limited failure logging
        self.max_inflight = int(max_inflight)
        self.shed_retry_after_s = float(shed_retry_after_s)
        self.default_deadline_ms = default_deadline_ms
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self.counters = ErrorCounters(
            "shed", "deadline_exceeded", "breaker_open", "degraded",
            "query_errors", "warmup_errors", "sniffer_errors",
            "feedback_errors", "reload_failed", "drained",
            "drain_abandoned",
        )
        # graceful drain (SIGTERM / POST /stop): /readyz flips to draining,
        # new queries shed, in-flight work finishes inside the budget
        self._draining = False
        self.drain_timeout_ms = float(
            os.environ.get("PIO_DRAIN_TIMEOUT_MS", 5000.0)
        )
        self._rl_log = RateLimitedLogger(logger)
        # the feedback poster rides the shared retry/breaker policy: a dead
        # event server trips the breaker and feedback drops fast (counted)
        # instead of each event burning max_attempts × timeout
        self._feedback_policy = RetryPolicy(max_attempts=3, base_backoff_s=0.1)
        self._feedback_breaker = CircuitBreaker(
            "feedback", failure_threshold=5, reset_timeout_s=15.0
        )
        # degraded fallback: the most recent good (jsonable) prediction per
        # nothing-else-available queries; a scorer/model failure serves this
        # with {"degraded": true} instead of a 500
        self._last_good: Optional[dict] = None
        self._reload_degraded = False
        # AOT fastpath warmup: every bucket rung compiles at deploy/reload,
        # BEFORE the generation swap, so no live request ever pays
        # trace/compile latency.  Default follows `batching` (the fastpath
        # only serves formed batches; a plain per-request server — most
        # tests — skips the per-bucket compiles); pass warm_fastpath
        # explicitly to override either way.
        self._warm_fastpath = (
            batching if warm_fastpath is None else bool(warm_fastpath)
        )
        # /readyz reports whether the LIVE generation actually finished its
        # warmup compiles (routers gate admission on *warm*, not merely
        # *loaded*).  True when warmup is not configured: a server that never
        # warms is as warm as it will ever be.
        self._fastpath_warm = not self._warm_fastpath
        # skew hot path (ISSUE 6): result cache for identical queries +
        # single-flight coalescing at the batcher.  Both default from env
        # knobs (PIO_RESULT_CACHE / PIO_COALESCE, off-by-default-safe);
        # pass result_cache=ResultCache(...) or coalesce=True to force.
        # Must exist before the first reload(): a reload bumps the serving
        # generation and flushes the cache.
        self._result_cache = (
            result_cache_from_env() if result_cache is None else result_cache
        )
        self._coalesce = (
            coalesce_from_env() if coalesce is None else bool(coalesce)
        )
        # model-generation tag: every successful swap increments it, so
        # cached answers from the previous generation can never validate
        # even if clear() were to race a concurrent put
        self._serving_gen = 0
        # (generation, spans) memo behind _pod_lockstep(): whether the
        # live fastpath's pod mesh spans jax.distributed processes — such
        # a replica can only be driven in SPMD lockstep and must refuse
        # independently routed queries (guarded by _lock)
        self._pod_lockstep_memo: Optional[tuple] = None
        # on-demand profiler (POST /debug/profile): one capture at a time
        # (jax.profiler is process-global), bounded window, counted
        self._profile_lock = threading.Lock()
        self._profile_captures = 0
        self._profile_last_unix = 0.0
        # streaming micro-generations (PIO_STREAMING=1): per-replica delta
        # state dict built by enable_streaming() after each successful
        # deploy/reload; None whenever streaming is off or no foldable
        # model is live — every streaming touchpoint no-ops on None, which
        # is what makes PIO_STREAMING=0 bit-identical to the pre-streaming
        # server
        self._streaming: Optional[dict] = None
        # multi-tenancy (ISSUE 19): tenant registry consulted on every
        # /queries.json — access-key auth, fair-share admission ahead of
        # the server-wide gate, per-tenant breakers/SLO/variant metrics.
        # None (PIO_TENANTS unset) keeps the open single-tenant server.
        self._tenants = (
            tenants_from_env(total_inflight=self.max_inflight)
            if tenants is None else tenants
        )
        # composed retrieval→ranking pipeline: the sealed config loads
        # here; the ENGINE binds against the deployed model on every
        # generation swap (_note_generation_swap).  None ⇒ single-stage.
        self._pipeline_config = (
            pipeline_from_env() if pipeline is None else pipeline
        )
        self._pipeline_engine = None
        self._register_routes()
        self.reload()
        self._batcher = None
        if batching:
            from predictionio_tpu.serving import fastpath
            from predictionio_tpu.serving.batching import MicroBatcher

            buckets = _batch_buckets(
                self._deployed.algorithms if self._deployed else (),
                fastpath.BUCKETS)
            self._batcher = MicroBatcher(
                self._run_query_batch, max_batch=max_batch, buckets=buckets,
            )
        if self.telemetry is not None:
            self._register_metrics()

    # -- model lifecycle -----------------------------------------------------
    def reload(self, instance_id: Optional[str] = None,
               force: bool = False) -> str:
        """(Re)load the latest COMPLETED instance; atomic swap.

        ``instance_id`` pins the load to ONE specific generation — the
        canary controller's hot-swap primitive (roll the canary replica to
        the candidate, roll it back to the baseline) — and refuses a
        QUARANTINED id unless ``force`` is set (operator override).  With
        no ``instance_id`` the newest non-quarantined COMPLETED instance
        deploys; a cold start additionally honors ``PIO_PIN_INSTANCE``
        (injected by the fleet while a canary is in flight) so autoscaler
        scale-ups spawn on the verified baseline, never the candidate.

        Graceful degradation: when a RELOAD fails (storage down, corrupt
        blob, bad hot-swap) and a previous generation is live, the server
        KEEPS SERVING the last good generation — counted, flagged on
        ``/readyz`` and stats — instead of dying or swapping in garbage.
        A COLD START whose newest blob is unusable falls back to the
        persisted last-known-good pointer (then any older COMPLETED
        generation); only a cold start with nothing deployable left fails
        loudly.

        Fast-path warm-up (``batching``) is part of the load, not an
        afterthought: a generation whose bucket programs cannot compile
        would answer every query from the host fall-through without one
        dispatch to the device.  So a warm-up failure at COLD START
        propagates (``pio deploy`` exits non-zero — there is no earlier
        generation to protect), and at RELOAD it is a failed reload: the
        previous, warm generation keeps serving, flagged ``reloadDegraded``.
        """
        if instance_id is None and self._deployed is None:
            pin = os.environ.get("PIO_PIN_INSTANCE", "").strip()
            if pin:
                instance_id = pin
        instance = None
        try:
            if instance_id is not None:
                if not force and persistence.is_quarantined(
                    instance_id, self.engine_id, self.engine_version,
                    self.engine_variant,
                ):
                    raise RuntimeError(
                        f"engine instance {instance_id} is quarantined "
                        "(failed online verification); pass force to "
                        "override"
                    )
                instance = self.storage.get_meta_data_engine_instances().get(
                    instance_id
                )
                if instance is None:
                    raise RuntimeError(
                        f"no engine instance {instance_id}"
                    )
            else:
                instance = get_latest_completed_instance(
                    self.storage, self.engine_id, self.engine_version,
                    self.engine_variant,
                )
            _, algorithms, serving, models = prepare_deploy(
                self.engine, instance, storage=self.storage, ctx=self.ctx
            )
        except Exception:
            with self._lock:
                last_good = self._deployed
            if last_good is not None:
                self.counters.inc("reload_failed")
                with self._lock:
                    self._reload_degraded = True
                self._rl_log.exception(
                    "reload", "reload failed; serving last good instance %s",
                    last_good.instance_id,
                )
                return last_good.instance_id
            # cold start: nothing in memory to keep serving — reach for the
            # on-disk last-known-good pointer, then older COMPLETED runs
            fallback = self._cold_start_fallback(
                failed_id=instance.id if instance is not None else None
            )
            if fallback is None:
                raise  # truly nothing deployable
            return fallback.instance_id
        if self._warm_fastpath:
            # pre-compile the serving fast path at deploy/reload so no live
            # request ever pays trace/compile latency (ISSUE: AOT warmup)
            try:
                for algo, model in zip(algorithms, models):
                    warm = getattr(algo, "warmup", None)
                    if warm is not None:
                        warm(model)
            except Exception:
                self.counters.inc("warmup_errors")
                with self._lock:
                    last_good = self._deployed
                if last_good is None:
                    raise  # cold start: nothing to keep serving
                self.counters.inc("reload_failed")
                with self._lock:
                    self._reload_degraded = True
                self._rl_log.exception(
                    "warmup", "fastpath warmup failed for instance %s; "
                    "serving last good instance %s",
                    instance.id, last_good.instance_id,
                )
                return last_good.instance_id
        deployed = _Deployed(
            instance_id=instance.id,
            algorithms=algorithms,
            serving=serving,
            models=models,
            start_time=time.time(),
        )
        with self._lock:
            self._deployed = deployed
            # reached only with every warm-up done (or none configured)
            self._fastpath_warm = True
        self._note_generation_swap()
        with self._lock:
            self._reload_degraded = False
        self._record_last_known_good(instance.id)
        # a new base generation subsumes all prior micro-generations:
        # re-base the delta pipeline on the freshly deployed factors
        self.enable_streaming()
        logger.info("deployed engine instance %s", instance.id)
        return instance.id

    def _note_generation_swap(self) -> None:
        """A new model generation is live: bump the serving generation (the
        result cache's model tag) and flush — answers computed against the
        previous generation must never be served against this one."""
        # handler threads read the generation per query; the bump comes
        # from reload/cold-start threads, so it takes the server lock
        with self._lock:
            self._serving_gen += 1
            deployed = self._deployed
        if self._result_cache is not None:
            self._result_cache.clear()
        # re-bind the pipeline against the new generation's algorithms/
        # models; a config that cannot bind (template without the ALS
        # surface) degrades to single-stage serving, never fails a swap
        if self._pipeline_config is not None and deployed is not None:
            try:
                engine = build_pipeline_engine(
                    self._pipeline_config, deployed.algorithms,
                    deployed.models,
                )
            except Exception:
                engine = None
                self._rl_log.exception(
                    "pipeline", "pipeline %s failed to bind; serving "
                    "single-stage", self._pipeline_config.name,
                )
            with self._lock:
                self._pipeline_engine = engine

    # -- last-known-good pointer (survives restarts) -------------------------
    def _lkg_path(self) -> str:
        from predictionio_tpu.utils.fs import pio_base_dir

        raw = f"{self.engine_id}-{self.engine_version}-{self.engine_variant}"
        safe = "".join(c if c.isalnum() or c in "-_." else "_" for c in raw)
        return os.path.join(pio_base_dir(), "last_known_good", safe + ".json")

    def _record_last_known_good(self, instance_id: str) -> None:
        """Persist the generation that just deployed successfully; a future
        cold start with a torn newest blob deploys this one instead.
        Best-effort: pointer write failure must never fail a deploy."""
        from predictionio_tpu.utils.fs import atomic_write_text

        path = self._lkg_path()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            atomic_write_text(
                path, json.dumps({"instanceId": instance_id})
            )
        except OSError:
            logger.debug("last-known-good pointer write failed", exc_info=True)

    def _read_last_known_good(self) -> Optional[str]:
        try:
            with open(self._lkg_path(), "r", encoding="utf-8") as f:
                value = json.load(f).get("instanceId")
            return value if isinstance(value, str) else None
        except (OSError, ValueError):
            return None

    def _cold_start_fallback(self, failed_id: Optional[str]) -> Optional[_Deployed]:
        """Deploy an older generation when the newest is unusable at cold
        start: the persisted last-known-good pointer first, then every
        other COMPLETED instance newest-first. Serving stale beats not
        serving; the swap is flagged degraded on /readyz and counted."""
        try:
            completed = self.storage.get_meta_data_engine_instances().get_completed(
                self.engine_id, self.engine_version, self.engine_variant
            )
        except Exception:
            return None
        # quarantined generations failed ONLINE verification (canary
        # rollback) — the LKG pointer and the newest-first walk both skip
        # them, or a restart would re-deploy the exact generation the
        # canary just rolled back
        quarantined = persistence.quarantined_instance_ids(
            self.engine_id, self.engine_version, self.engine_variant
        )
        by_id = {i.id: i for i in completed}
        order: list[str] = []
        lkg_id = self._read_last_known_good()
        if (lkg_id and lkg_id != failed_id and lkg_id in by_id
                and lkg_id not in quarantined):
            order.append(lkg_id)
        for inst in completed:
            if (inst.id != failed_id and inst.id not in order
                    and inst.id not in quarantined):
                order.append(inst.id)
        for iid in order:
            try:
                _, algorithms, serving, models = prepare_deploy(
                    self.engine, by_id[iid], storage=self.storage, ctx=self.ctx
                )
            except Exception:
                self._rl_log.exception(
                    "reload", "fallback candidate %s failed to deploy", iid
                )
                continue
            deployed = _Deployed(
                instance_id=iid,
                algorithms=algorithms,
                serving=serving,
                models=models,
                start_time=time.time(),
            )
            with self._lock:
                self._deployed = deployed
                # the fallback path deploys without running warmup
                self._fastpath_warm = not self._warm_fastpath
            self._note_generation_swap()
            self.counters.inc("reload_failed")
            with self._lock:
                self._reload_degraded = True
            self._record_last_known_good(iid)
            logger.warning(
                "cold start: newest instance %s unusable; serving "
                "last-known-good %s (degraded)", failed_id, iid,
            )
            return deployed
        return None

    # -- observability -------------------------------------------------------
    # -- streaming micro-generations (crash-safe delta pipeline) -------------
    def enable_streaming(
        self, delta_dir: Optional[str] = None
    ) -> Optional[dict]:
        """Wire this replica into the sealed delta log (PIO_STREAMING=1).

        Finds the first deployed factor model, fingerprints its base
        generation, and builds the fenced :class:`DeltaApplier` over the
        per-generation delta log.  Catch-up runs SYNCHRONOUSLY here —
        before the caller (deploy/reload) lets ``/readyz`` go ready — so
        a crash-restarted or freshly autoscaled replica is readmitted
        only at the fleet's epoch, never behind it.  Returns the
        applier's stats, or None when streaming is off or no foldable
        model is deployed.
        """
        self._stop_streaming()
        if not _delta.streaming_enabled():
            return None
        with self._lock:
            d = self._deployed
        if d is None:
            return None
        target = None
        for algo, model in zip(d.algorithms, d.models):
            if (
                getattr(model, "user_factors", None) is not None
                and getattr(model, "item_factors", None) is not None
                and getattr(model, "user_map", None) is not None
            ):
                target = (algo, model)
                break
        if target is None:
            return None
        algo, model = target
        fp = _delta.model_fingerprint(model.user_factors, model.item_factors)
        directory = delta_dir or _delta.delta_dir_for(fp)
        delta_log = _delta.DeltaLog(directory)
        st: dict = {
            "algo": algo,
            "model": model,
            "log": delta_log,
            "dir": directory,
            "fingerprint": fp,
            # replica-local cooccurrence count accumulator (pair -> count)
            "cooc": {},
            "slo_ms": float(os.environ.get("PIO_FRESHNESS_SLO_MS", "5000")),
            "degraded_served": 0,
            "staleness_ms": 0.0,
            "staleness_checked": 0.0,
            "wedged": None,
            "wake": threading.Event(),
            "stop": threading.Event(),
            "thread": None,
        }
        st["applier"] = _delta.DeltaApplier(
            fp,
            lambda dl: self._apply_streaming_delta(st, dl),
            delta_log=delta_log,
        )
        # single-writer rebind: enable runs on the deploy/reload thread
        # before the catch-up worker starts; readers see None or a fully
        # built state dict, never a partial one
        self._streaming = st  # pio: ignore[race-unguarded-rebind]
        # catch-up before readmission: replay every already-sealed epoch
        # while /readyz still answers not-ready for this generation
        self._streaming_catch_up(st)
        t = threading.Thread(
            target=self._catchup_loop,
            name="queryserver-delta-catchup",
            daemon=True,
        )
        st["thread"] = t
        t.start()
        logger.info(
            "streaming enabled: base %s, delta log %s, epoch %d",
            fp, directory, st["applier"].applied_epoch,
        )
        return st["applier"].stats()

    def _stop_streaming(self) -> None:
        st = self._streaming
        self._streaming = None
        if st is not None:
            st["stop"].set()
            st["wake"].set()

    def _apply_streaming_delta(self, st: dict, dl) -> None:
        """In-place application of one fenced delta (DeltaApplier's
        apply_fn): device factor buffers first, then the host-side model
        copies, the cooccurrence counts, and the entity-targeted result
        cache invalidation.  Bucket shapes never change, so nothing here
        can trigger a recompile."""
        import numpy as np

        algo, model = st["algo"], st["model"]
        user_idx = np.asarray(dl.user_idx, dtype=np.int64)
        item_idx = (
            np.asarray(dl.item_idx, dtype=np.int64)
            if dl.item_idx is not None
            else np.zeros((0,), np.int64)
        )
        scorer = getattr(algo, "_fastpath", None)
        if scorer is not None:
            scorer.apply_delta_rows(
                dl.user_idx, dl.user_rows,
                item_idx=dl.item_idx, item_rows=dl.item_rows,
            )
        # host factors track the delta so the next reload's last-known-good
        # comparisons, fold-in gates and fallback paths all see fresh rows
        if user_idx.size:
            model.user_factors[user_idx] = np.asarray(
                dl.user_rows, dtype=model.user_factors.dtype
            )
        if item_idx.size:
            model.item_factors[item_idx] = np.asarray(
                dl.item_rows, dtype=model.item_factors.dtype
            )
        # ALSScorer's own lazy device copies (the unbatched _score_batch
        # path): U/V ride as call arguments, so a functional row patch
        # swaps data without touching any compiled executable
        dev_u = getattr(algo, "_U", None)
        if dev_u is not None and user_idx.size:
            algo._U = dev_u.at[user_idx].set(
                np.asarray(dl.user_rows).astype(dev_u.dtype)
            )
        dev_v = getattr(algo, "_V", None)
        if dev_v is not None and item_idx.size:
            algo._V = dev_v.at[item_idx].set(
                np.asarray(dl.item_rows).astype(dev_v.dtype)
            )
        if dl.cooc_updates is not None and len(dl.cooc_updates):
            from predictionio_tpu.models.cooccurrence import fold_increments

            fold_increments(dl.cooc_updates, st["cooc"])
        # entity-targeted: only the users this delta rewrote lose their
        # cached answers; everyone else stays hot
        from predictionio_tpu.serving import result_cache as _rc

        _rc.notify_delta(dl.user_ids)

    def _streaming_staleness_ms(self) -> float:
        """Age of the oldest sealed-but-unapplied epoch, cached for 250ms
        so the per-query SLO check never turns into a per-query listdir."""
        st = self._streaming
        if st is None:
            return 0.0
        now = time.monotonic()
        if now - st["staleness_checked"] >= 0.25:
            try:
                age = st["log"].oldest_unapplied_age_s(
                    st["applier"].applied_epoch
                )
            except OSError:
                age = 0.0
            st["staleness_ms"] = age * 1000.0
            st["staleness_checked"] = now
        return st["staleness_ms"]

    def _catchup_loop(self) -> None:
        """Delta catch-up worker: paces on Event.wait (woken early by
        /readyz when it spots the log ahead of us) and delegates the
        blob I/O to the applier."""
        st = self._streaming
        if st is None:
            return
        pace_s = float(os.environ.get("PIO_DELTA_CATCHUP_MS", "1000")) / 1e3
        while not st["stop"].is_set():
            st["wake"].wait(pace_s)
            st["wake"].clear()
            if st["stop"].is_set():
                return
            self._streaming_catch_up(st)

    def _streaming_catch_up(self, st: dict) -> None:
        try:
            rc = st["applier"].catch_up()
        except Exception:
            self._rl_log.exception("delta", "delta catch-up failed")
            return
        # a refused catch-up (torn blob, fingerprint fence, gap) wedges
        # at the last good epoch: remember the receipt so /readyz stops
        # holding the replica out — it serves degraded instead of
        # flapping between 503 and a replay that can never succeed
        st["wedged"] = rc if rc.get("refused") else None

    def streaming_stats(self) -> Optional[dict]:
        st = self._streaming
        if st is None:
            return None
        out = st["applier"].stats()
        out.update(
            log_epoch=st["log"].last_epoch(),
            staleness_ms=self._streaming_staleness_ms(),
            slo_ms=st["slo_ms"],
            degraded_served=st["degraded_served"],
            cooc_pairs=len(st["cooc"]),
            fingerprint=st["fingerprint"],
            dir=st["dir"],
        )
        return out

    def _fastpath_stats(self) -> Optional[dict]:
        """First deployed algorithm's serving_stats (registry bridge)."""
        with self._lock:
            d = self._deployed
        if d is None:
            return None
        for algo, model in zip(d.algorithms, d.models):
            get_stats = getattr(algo, "serving_stats", None)
            if get_stats is None:
                continue
            s = get_stats(model)
            if s is not None:
                return s
        return None

    def _pod_lockstep(self) -> bool:
        """True when the live fastpath's pod mesh spans processes.

        Such a mesh is bound by the SPMD dispatch contract (every
        ``jax.distributed`` process must execute the same compiled
        program for the same batch in the same order — the cross-host
        leaderboard gather is a collective ALL peers participate in), so
        this replica cannot answer queries routed to it alone: the first
        independent dispatch would wedge the whole pod in the collective.
        ``/queries.json`` refuses with 503 and ``/readyz`` reports
        not-ready instead; lockstep drivers (the pod bench harness, batch
        scoring run identically on every process) call the scorer
        directly and are unaffected.  Memoized per serving generation —
        the flag is a property of the deployed scorer's placement.
        """
        with self._lock:
            gen = self._serving_gen
            memo = self._pod_lockstep_memo
        if memo is not None and memo[0] == gen:
            return memo[1]
        pod = (self._fastpath_stats() or {}).get("pod") or {}
        spans = bool(pod.get("spans_processes"))
        with self._lock:
            self._pod_lockstep_memo = (gen, spans)
        return spans

    def _event_cache_stats(self) -> Optional[dict]:
        """First deployed algorithm's ServingEventCache stats, if any (the
        e-commerce template creates one lazily on its first predict)."""
        with self._lock:
            d = self._deployed
        if d is None:
            return None
        for algo in d.algorithms:
            cache = getattr(algo, "_event_cache", None)
            if cache is not None:
                return cache.stats_dict()
        return None

    @staticmethod
    def _train_kernel_stats() -> Optional[dict]:
        """Training-kernel dispatch stats recorded by the most recent
        in-process train (None until one runs)."""
        from predictionio_tpu.ops import train_kernel

        return train_kernel.stats() or None

    def _register_metrics(self) -> None:
        """Expose every scattered serving stat on the obs registry, making
        ``/metrics`` the single source of truth for this server."""
        reg = self.telemetry.registry
        _bridges.bridge_error_counters(
            reg, "pio_query_errors_total",
            "Serving failures by kind (shed, deadline 504, breaker_open, "
            "degraded, query/warmup/sniffer/feedback/reload).",
            self.counters,
        )
        _bridges.bridge_latency_histogram(
            reg, "pio_query_latency_seconds",
            "handle_query latency, bridged from the serving histogram.",
            self.latency,
        )
        reg.gauge_fn(
            "pio_query_inflight",
            "Queries currently inside the admission gate.",
            lambda: float(self._inflight),
        )
        reg.gauge_fn(
            "pio_query_max_inflight",
            "Admission-control bound; at or beyond it requests shed (503).",
            lambda: float(self.max_inflight),
        )
        if self._batcher is not None:
            _bridges.bridge_batcher(reg, self._batcher.stats)
        _bridges.bridge_fastpath(reg, self._fastpath_stats)
        # pio_shard_*: emits only while a ShardingPlan is live (the stats
        # block is absent under replicated placement)
        _bridges.bridge_sharding(reg, self._fastpath_stats)
        # pio_ivf_*: emits only while an IVF index is live (the stats
        # block is absent under exact retrieval)
        _bridges.bridge_ivf(reg, self._fastpath_stats)
        # pio_pod_*: emits only while a pod (multi-host-group) plan is
        # live — the fastpath publishes a "pod" stats block then
        _bridges.bridge_pod(
            reg, lambda: (self._fastpath_stats() or {}).get("pod")
        )
        # live device utilization: the scorer's cost-annotated dispatch
        # accountant, labeled with the generation it serves (the scorer —
        # and its accountant — are rebuilt on every successful reload)
        _bridges.bridge_devprof(
            reg,
            lambda: (self._fastpath_stats() or {}).get("devprof"),
            lambda: self._serving_gen,
        )
        # pio_train_kernel_*: the fused-training-kernel dispatch recorded
        # by the most recent in-process train (empty — and silent — until
        # one runs, e.g. the template train-then-serve flow)
        _bridges.bridge_train_kernel(reg, self._train_kernel_stats)
        if self._result_cache is not None:
            _bridges.bridge_result_cache(reg, self._result_cache.stats)
        reg.gauge_fn(
            "pio_result_cache_enabled",
            "1 when the serving result cache is active.",
            lambda: 0.0 if self._result_cache is None else 1.0,
        )
        reg.gauge_fn(
            "pio_coalesce_enabled",
            "1 when single-flight coalescing of identical queries is on.",
            lambda: 1.0 if self._coalesce else 0.0,
        )
        _bridges.bridge_event_cache(reg, self._event_cache_stats)
        # pio_tenant_*: emits only while a tenant registry is installed
        # (PIO_TENANTS unset keeps /metrics byte-identical); tenant and
        # variant labels ride under the PIO_METRICS_MAX_SERIES cap like
        # every other labeled family
        if self._tenants is not None:
            _bridges.bridge_tenancy(reg, self._tenants.stats)
        # pio_pipeline_*: emits only while a composed pipeline is bound
        _bridges.bridge_pipeline(
            reg,
            lambda: (
                self._pipeline_engine.stats()
                if self._pipeline_engine is not None else None
            ),
        )
        _bridges.bridge_resilience(
            reg,
            lambda: {"breakers": [self._feedback_breaker.stats()]},
            prefix="pio_feedback",
        )
        storage_rs = getattr(self.storage, "resilience_stats", None)
        if callable(storage_rs):
            _bridges.bridge_resilience(reg, storage_rs)

        def _serving_families():
            with self._lock:
                rc = self.request_count
                avg = self.avg_serving_sec
                last = self.last_serving_sec
                dropped = self._feedback_dropped
            F = _bridges.Family
            return [
                F("pio_query_requests_total", "counter",
                  "Queries served by the predict hot loop.",
                  [("", (), float(rc))]),
                F("pio_query_avg_serving_seconds", "gauge",
                  "Running mean serving seconds (parity: CreateServer "
                  "avg gauge).", [("", (), float(avg))]),
                F("pio_query_last_serving_seconds", "gauge",
                  "Most recent serving seconds.", [("", (), float(last))]),
                F("pio_feedback_dropped_total", "counter",
                  "Feedback events dropped on a full queue.",
                  [("", (), float(dropped))]),
                F("pio_reload_degraded", "gauge",
                  "1 while serving the last good generation after a "
                  "failed reload.",
                  [("", (), 1.0 if self._reload_degraded else 0.0)]),
                F("pio_draining", "gauge",
                  "1 while the server is draining toward shutdown.",
                  [("", (), 1.0 if self._draining else 0.0)]),
                F("pio_profile_captures_total", "counter",
                  "On-demand jax.profiler captures served by "
                  "POST /debug/profile.",
                  [("", (), float(self._profile_captures))]),
                F("pio_profile_last_capture_unix", "gauge",
                  "Wall-clock time of the most recent profile capture "
                  "(0 when none has run).",
                  [("", (), float(self._profile_last_unix))]),
            ]

        reg.register_collector(_serving_families)

        def _streaming_families():
            # emits only while streaming is live: PIO_STREAMING=0 keeps
            # /metrics byte-identical to the pre-streaming server
            st = self._streaming
            if st is None:
                return []
            a = st["applier"].stats()
            refused = a["refused"] or {}
            F = _bridges.Family
            return [
                F("pio_delta_epoch", "gauge",
                  "Micro-generation epoch applied by this replica.",
                  [("", (), float(a["applied_epoch"]))]),
                F("pio_delta_log_epoch", "gauge",
                  "Newest epoch sealed in this replica's delta log.",
                  [("", (), float(st["log"].last_epoch()))]),
                F("pio_delta_applied_total", "counter",
                  "Deltas applied in place on the serving factors.",
                  [("", (), float(a["applied"]))]),
                F("pio_delta_noop_total", "counter",
                  "Replayed already-applied epochs acked as no-ops "
                  "(the exactly-once path).",
                  [("", (), float(a["noops"]))]),
                F("pio_delta_refused_total", "counter",
                  "Deltas refused by reason (fingerprint fence, gap, "
                  "integrity).",
                  [("", (("reason", r),), float(n))
                   for r, n in sorted(refused.items())] or
                  [("", (("reason", "none"),), 0.0)]),
                F("pio_delta_cooc_pending", "gauge",
                  "Distinct cooccurrence pairs accumulated from applied "
                  "deltas since the last full retrain.",
                  [("", (), float(len(st["cooc"])))]),
                F("pio_freshness_staleness_ms", "gauge",
                  "Age of the oldest sealed-but-unapplied delta epoch.",
                  [("", (), float(self._streaming_staleness_ms()))]),
                F("pio_freshness_slo_ms", "gauge",
                  "Configured freshness SLO (PIO_FRESHNESS_SLO_MS).",
                  [("", (), float(st["slo_ms"]))]),
                F("pio_freshness_visible_p99_ms", "gauge",
                  "p99 event-committed to prediction-visible latency "
                  "over recent applied deltas.",
                  [("", (), float(a["visible_p99_ms"]))]),
                F("pio_freshness_degraded_total", "counter",
                  "Answers served with degraded:true because staleness "
                  "exceeded the freshness SLO.",
                  [("", (), float(st["degraded_served"]))]),
            ]

        reg.register_collector(_streaming_families)

    # -- batched path: one Algorithm.batch_predict pass for N queries --------
    def _run_query_batch(self, queries: list) -> list:
        with self._lock:
            deployed = self._deployed
        with _tracing.stage("batch_assembly"):
            supplemented = [
                (i, deployed.serving.supplement(q))
                for i, q in enumerate(queries)
            ]
        per_algo = [
            dict(algo.batch_predict(model, supplemented))
            for algo, model in zip(deployed.algorithms, deployed.models)
        ]
        out = []
        # the batcher charges to `postprocess` whatever part of the run no
        # stage covers (the rest of batch_predict); naming this part puts
        # it on the profiler's clock
        with _tracing.stage("postprocess"):
            for i, (_, sq) in enumerate(supplemented):
                preds = [d[i] for d in per_algo if i in d]
                # pair the supplemented query with its prediction so the
                # serving pipeline downstream of the batch (plugins,
                # feedback) sees the same supplemented query as the
                # unbatched path
                out.append((sq, deployed.serving.serve(sq, preds)))
        return out

    # -- degraded fallback ---------------------------------------------------
    def _fallback_result(self, query: Any, deployed: _Deployed) -> Optional[dict]:
        """Best degraded answer when the scorer fails.

        Preference order: an algorithm's own ``fallback_predict`` (e.g. a
        popularity list computed at train time), else the last good
        prediction this server produced (stale beats empty for a
        recommendation surface).  None ⇒ no fallback, caller 500s.
        """
        for algo, model in zip(deployed.algorithms, deployed.models):
            fb = getattr(algo, "fallback_predict", None)
            if fb is None:
                continue
            try:
                out = _to_jsonable(fb(model, query))
                if isinstance(out, dict):
                    return out
            except Exception:
                self._rl_log.exception(
                    "fallback", "fallback_predict failed for %s",
                    type(algo).__name__,
                )
        if self._last_good is not None:
            return dict(self._last_good)
        return None

    # -- query hot loop (parity: CreateServer.scala:484-634) -----------------
    def handle_query(
        self,
        data: dict,
        deadline: Optional[Deadline] = None,
        tenant: Optional[str] = None,
        variant: Optional[str] = None,
    ) -> dict:
        t0 = time.perf_counter()
        with self._lock:
            deployed = self._deployed
            pipe = self._pipeline_engine
        with _tracing.stage("decode"):
            query = bind_query(self.engine.query_cls, data)
        degraded = False
        cache = self._result_cache
        # one canonical fingerprint serves both layers: the result-cache
        # key here and the single-flight coalescing key at the batcher.
        # Under multi-tenancy the fingerprint is NAMESPACED by tenant +
        # A/B variant + live engine instance: identical bodies from two
        # tenants must never share a cache entry or a coalesced leader
        # slot (cross-tenant answer leakage)
        namespace = None
        if tenant is not None:
            namespace = "\x1f".join(
                (tenant, variant or "-",
                 deployed.instance_id if deployed else "")
            )
        fp = (
            canonical_fingerprint(data, namespace=namespace)
            if (cache is not None or self._coalesce)
            else None
        )
        cache_hit = False
        if cache is not None and fp is not None:
            cached = cache.get(fp, self._serving_gen)
            if cached is not None:
                cache_hit = True
                result = cached
                # no supplemented form exists on a hit; plugins and
                # feedback see the bound query, as on the degraded path
                supplemented = query
        # flight-recorder context: which generation answered and whether
        # the device was skipped (a cache hit never dispatches — its trace
        # must carry no device stages)
        for t in _tracing.active_traces():
            t.annotate(
                generation=self._serving_gen,
                **(
                    {"cache": "hit" if cache_hit else "miss"}
                    if cache is not None
                    else {}
                ),
            )
        if not cache_hit:
            try:
                if deadline is not None and deadline.expired():
                    raise DeadlineExceeded("deadline expired before predict")
                pmeta = None
                if pipe is not None:
                    # composed dataflow: retrieval → ranking under
                    # per-stage shares of this request's deadline; a
                    # late/failed ranking stage yields the retrieval-only
                    # answer with degraded:true instead of blowing the SLO
                    supplemented = deployed.serving.supplement(query)
                    prediction, pmeta = pipe.run_pipeline(
                        supplemented, deadline
                    )
                    prediction = deployed.serving.serve(
                        supplemented, [prediction]
                    )
                elif self._batcher is not None:
                    supplemented, prediction = self._batcher.submit(
                        query, deadline=deadline,
                        key=fp if self._coalesce else None,
                    )
                else:
                    supplemented = deployed.serving.supplement(query)
                    predictions = [
                        algo.predict(model, supplemented)
                        for algo, model in zip(
                            deployed.algorithms, deployed.models
                        )
                    ]
                    prediction = deployed.serving.serve(
                        supplemented, predictions
                    )
                with _tracing.stage("serialize"):
                    result = _to_jsonable(prediction)
                if pmeta is not None and pmeta.get("degraded"):
                    # a stage overran its deadline share: the answer is
                    # retrieval-only — flagged, counted, never cached
                    # (it must not outlive the pressure that caused it)
                    if isinstance(result, dict):
                        result["degraded"] = True
                        result["pipelineStage"] = pmeta.get("stage")
                    degraded = True
                    self.counters.inc("degraded")
            except DeadlineExceeded:
                self.counters.inc("deadline_exceeded")
                raise
            except TypeError:
                # malformed query values are a CLIENT bug: surface them
                # through the route's TypeError → 400 mapping, never mask
                # them behind a stale degraded 200 (which would also pollute
                # the `degraded` counter, which reads as a server
                # regression)
                self.counters.inc("query_errors")
                raise
            except Exception as e:
                # scorer/model failure: serve the degraded fallback rather
                # than a 500 — availability beats freshness for serving
                fallback = self._fallback_result(query, deployed)
                if fallback is None:
                    self.counters.inc("query_errors")
                    raise
                self.counters.inc("degraded")
                self._rl_log.warning(
                    "degraded", "prediction failed (%s); serving degraded "
                    "fallback", e,
                )
                result = fallback
                result["degraded"] = True
                supplemented = query
                degraded = True
        if not degraded:
            # remember the newest good answer for the degraded path; shallow
            # copy so prId/plugin rewrites never leak back into the cache
            if isinstance(result, dict):
                # every handler thread writes this; order the rebinds so
                # the degraded path always sees a complete answer
                with self._lock:
                    self._last_good = dict(result)
            if (
                cache is not None
                and fp is not None
                and not cache_hit
                and isinstance(result, dict)
            ):
                # store the pre-plugin, pre-prId answer: plugins rewrite
                # per caller and run on every hit; degraded answers are
                # never cached (they would outlive the failure)
                cache.put(
                    fp, result,
                    entity_ids_from(data, cache.key_fields),
                    self._serving_gen,
                )
        # freshness SLO: when the sealed delta log is ahead of this
        # replica by more than PIO_FRESHNESS_SLO_MS, the answer is still
        # served — annotated, never failed.  Runs AFTER cache.put (the
        # cache deep-copies, so the annotation never sticks to the cached
        # answer) and applies to hits too: a hot cache entry is exactly as
        # stale as the factors that computed it.
        st = self._streaming
        if st is not None and isinstance(result, dict):
            stale = self._streaming_staleness_ms()
            if stale > st["slo_ms"]:
                result["degraded"] = True
                result["staleness_ms"] = round(stale, 1)
                st["degraded_served"] += 1
                st["wake"].set()
        # plugins see JSON values, as in the reference (JValue-based process)
        for p in self.plugins:
            if p.plugin_type == EngineServerPlugin.OUTPUT_BLOCKER:
                result = p.process(supplemented, result, {})
        for p in self.plugins:
            if p.plugin_type == EngineServerPlugin.OUTPUT_SNIFFER:
                try:
                    p.process(supplemented, result, {})
                except Exception:
                    self.counters.inc("sniffer_errors")
                    self._rl_log.exception(
                        "sniffer", "sniffer plugin %s failed", p.name
                    )
        if self.feedback:
            pr_id = data.get("prId") or secrets.token_hex(8)
            result["prId"] = pr_id
            self._send_feedback(data, result, pr_id, deployed.instance_id)
        dt = time.perf_counter() - t0
        self.latency.observe(dt)
        with self._lock:
            self.request_count += 1
            self.last_serving_sec = dt
            self.avg_serving_sec += (dt - self.avg_serving_sec) / self.request_count
        return result

    def _send_feedback(self, query, prediction, pr_id, instance_id) -> None:
        """Async POST back to the event server (CreateServer.scala:563-569).

        Enqueues onto a bounded queue drained by one daemon worker — the
        request thread never blocks on the event server, and a slow or dead
        event server drops feedback (counted) instead of backing up serving.
        """
        if not self.event_server_url:
            return
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": {
                "engineInstanceId": instance_id,
                "query": query,
                "prediction": prediction,
            },
        }
        if self._feedback_worker is None:
            with self._lock:
                if self._feedback_worker is None:
                    self._feedback_worker = threading.Thread(
                        target=self._feedback_loop,
                        name="queryserver-feedback",
                        daemon=True,
                    )
                    self._feedback_worker.start()
        try:
            self._feedback_queue.put_nowait(event)
        except queue.Full:
            with self._lock:
                self._feedback_dropped += 1
            logger.warning("feedback queue full; dropping event %s", pr_id)

    def _feedback_loop(self) -> None:
        url = f"{self.event_server_url}/events.json"
        if self.access_key:
            url += f"?accessKey={self.access_key}"
        while True:
            event = self._feedback_queue.get()
            if event is None:  # sentinel from stop()
                return
            payload = json.dumps(event).encode()

            def post():
                req = urllib.request.Request(
                    url,
                    data=payload,
                    method="POST",
                    headers={"Content-Type": "application/json"},
                )
                # fire-and-forget by design: feedback is decoupled from
                # the request that produced it (the caller already got
                # its answer), so there is no deadline to propagate —
                # the fixed timeout + breaker bound the loop instead
                # pio: ignore[deadline-drop]
                urllib.request.urlopen(req, timeout=5)

            try:
                # pio: ignore[deadline-not-forwarded] (see post() above)
                call_with_resilience(
                    post,
                    self._feedback_policy,
                    breaker=self._feedback_breaker,
                )
            except BreakerOpen:
                # event server is down: drop fast (counted) instead of each
                # event burning max_attempts × timeout behind an open breaker
                self.counters.inc("breaker_open")
            except Exception:
                self.counters.inc("feedback_errors")
                self._rl_log.exception("feedback", "feedback POST failed")

    # -- routes ----------------------------------------------------------------
    def retry_after_s(self) -> float:
        """Backpressure-aware ``Retry-After``: ``shed_retry_after_s`` is
        the BASE.  While draining the hint is the drain budget (the
        earliest a replacement process could answer here); under load
        it scales with queue depth — inflight plus batcher backlog over
        the admission cap — so clients back off longer the deeper the
        overload.  Reads ``_inflight`` without its lock: a torn read
        costs at most one slightly-off hint, and one shed site calls
        this while already holding the lock."""
        if self._draining:
            return max(self.shed_retry_after_s, self.drain_timeout_ms / 1e3)
        depth = float(self._inflight)
        if self._batcher is not None:
            try:
                depth += float(self._batcher.stats().get("depth") or 0)
            except Exception:
                pass
        load = depth / float(max(1, self.max_inflight))
        return round(min(self.shed_retry_after_s * max(1.0, load), 30.0), 2)

    def _register_routes(self):
        svc = self.service

        @svc.route("GET", r"/")
        def index(req: Request):
            with self._lock:
                d = self._deployed
                info = {
                    "status": "alive",
                    "engineInstanceId": d.instance_id if d else None,
                    "engineVariant": self.engine_variant,
                    "startTime": d.start_time if d else None,
                    "requestCount": self.request_count,
                    "avgServingSec": self.avg_serving_sec,
                    "lastServingSec": self.last_serving_sec,
                    "latency": self.latency.summary(),
                    "feedback": self.feedback,
                    "feedbackDropped": self._feedback_dropped,
                }
                algorithms = d.algorithms if d else []
                models = d.models if d else []
            info["batching"] = (
                self._batcher.stats() if self._batcher is not None else None
            )
            info["resultCache"] = (
                self._result_cache.stats()
                if self._result_cache is not None
                else None
            )
            info["coalesce"] = self._coalesce
            info["tenancy"] = (
                self._tenants.stats() if self._tenants is not None else None
            )
            info["pipeline"] = (
                self._pipeline_engine.stats()
                if self._pipeline_engine is not None
                else None
            )
            fp = []
            for algo, model in zip(algorithms, models):
                get_stats = getattr(algo, "serving_stats", None)
                if get_stats is None:
                    continue
                s = get_stats(model)
                if s is not None:
                    fp.append(s)
            info["fastpath"] = fp or None
            with self._inflight_lock:
                inflight = self._inflight
            info["resilience"] = {
                "inflight": inflight,
                "maxInflight": self.max_inflight,
                "counters": self.counters.snapshot(),
                "feedbackBreaker": self._feedback_breaker.stats(),
                "reloadDegraded": self._reload_degraded,
            }
            return json_response(200, info)

        @svc.route("GET", r"/healthz")
        def healthz(req: Request):
            # liveness: the process is up and the route table answers
            return json_response(200, {"status": "ok"})

        @svc.route("GET", r"/readyz")
        def readyz(req: Request):
            # readiness: safe to route traffic here — a model is deployed
            # and the admission gate has headroom.  reloadDegraded is
            # reported but does NOT fail readiness: the last good
            # generation is still serving.
            with self._lock:
                dep = self._deployed
                deployed = dep is not None
                generation = self._serving_gen
                warm = self._fastpath_warm
            with self._inflight_lock:
                inflight = self._inflight
            body = {
                "deployed": deployed,
                "inflight": inflight,
                "maxInflight": self.max_inflight,
                "reloadDegraded": self._reload_degraded,
                "draining": self._draining,
                # router admission context: which model generation is live
                # and whether its warmup compiles completed — balancers gate
                # on *warm*, not merely *loaded*
                "generation": generation,
                "fastpathWarm": warm,
                # the durable identity of the live generation: the local
                # `generation` counter differs per process, so the canary
                # controller attributes per-generation metrics (and targets
                # hot-swaps) by engine instance id
                "engineInstanceId": dep.instance_id if dep else None,
            }
            # sharded placement: surface backend + plan fingerprint so a
            # rebalance is visible as a generation identity change to
            # anything probing readiness (pio shards, the fleet router)
            fps = self._fastpath_stats()
            if fps and fps.get("serving_backend"):
                body["servingBackend"] = fps["serving_backend"]
                plan = (fps.get("sharding") or {}).get("plan") or {}
                if plan.get("fingerprint"):
                    body["shardingFingerprint"] = plan["fingerprint"]
            # pod placement: advertise this replica's host group so the
            # fleet router can fan each query to the group that owns its
            # serving mesh (PIO_POD_GROUP pins the group in fleet
            # deployments of SELF-CONTAINED replicas).  A mesh that spans
            # jax.distributed processes is lockstep-only — advertising a
            # routable group would invite per-group batches its SPMD
            # peers never dispatch, wedging the cross-host collective —
            # so `group` is withheld (null) and the replica reports
            # not-ready below; PIO_POD_GROUP cannot override this.
            pod = (fps or {}).get("pod")
            pod_spans = bool((pod or {}).get("spans_processes"))
            if pod:
                group_env = os.environ.get("PIO_POD_GROUP", "")
                body["pod"] = {
                    "group": None if pod_spans
                    else int(group_env) if group_env.strip()
                    else int(pod.get("process_index") or 0),
                    "groups": int(pod.get("host_groups") or 1),
                    "fingerprint": pod.get("fingerprint"),
                    "processIndex": pod.get("process_index"),
                    "processCount": pod.get("process_count"),
                    "spansProcesses": pod_spans,
                }
            # streaming: expose the applied micro-generation epoch and
            # current staleness so the router/fleet can see exactly where
            # this replica sits in the delta sequence
            st = self._streaming
            delta_behind = False
            if st is not None:
                applied = st["applier"].applied_epoch
                head = st["log"].last_epoch()
                body["deltaEpoch"] = applied
                body["deltaLogEpoch"] = head
                body["stalenessMs"] = round(self._streaming_staleness_ms(), 1)
                # a wedged log (torn blob / fence refusal with no progress
                # since) must not hold the replica out forever: it rejoins
                # at its last good epoch and serves degraded instead
                wedged = st.get("wedged")
                stuck = (
                    wedged is not None
                    and applied <= int(wedged.get("applied_epoch", -1))
                )
                if stuck:
                    body["deltaWedged"] = wedged.get("reason")
                delta_behind = head > applied and not stuck
            # every not-ready answer carries Retry-After, as the shed paths
            # do — docs/operations.md promises the header on all 503s
            retry = {"Retry-After": f"{self.retry_after_s():g}"}
            if self._draining:
                body["status"] = "draining"
                return Response(status=503, body=body, headers=retry)
            if not deployed:
                body["status"] = "no engine instance deployed"
                return Response(status=503, body=body, headers=retry)
            if delta_behind:
                # catch-up before readmission: wake the worker and refuse
                # traffic until this replica reaches the fleet's epoch
                st["wake"].set()
                body["status"] = "delta catch-up"
                return Response(status=503, body=body, headers=retry)
            if inflight >= self.max_inflight:
                body["status"] = "overloaded"
                return Response(status=503, body=body, headers=retry)
            if pod_spans:
                # never admitted into a routed fleet: this process can
                # only score in SPMD lockstep with its pod peers
                body["status"] = "pod mesh spans processes (lockstep only)"
                return Response(status=503, body=body, headers=retry)
            body["status"] = "ready"
            return json_response(200, body)

        def _serve_admitted(req, data, tenant, variant):
            # admission control: beyond max_inflight, queueing only adds
            # latency to requests that will miss their deadlines anyway —
            # shed with 503 + Retry-After so callers back off
            with self._inflight_lock:
                if self._inflight >= self.max_inflight:
                    self.counters.inc("shed")
                    return Response(
                        status=503,
                        body={"message": "server overloaded; request shed"},
                        headers={"Retry-After": f"{self.retry_after_s():g}"},
                    )
                self._inflight += 1
            try:
                deadline = parse_deadline_header(req.headers.get(DEADLINE_HEADER))
                if deadline is None and self.default_deadline_ms is not None:
                    deadline = Deadline.after_ms(self.default_deadline_ms)
                if deadline is not None and deadline.expired():
                    # already over budget on arrival: never touches the device
                    self.counters.inc("deadline_exceeded")
                    return json_response(
                        504, {"message": "deadline expired before execution"}
                    )
                try:
                    # ambient binding: storage/cache hops under this
                    # request see the budget via current_deadline() even
                    # where no deadline parameter reaches them
                    with deadline_scope(deadline):
                        # untenanted servers keep the two-arg calling
                        # convention — handle_query is a documented
                        # wrap/override point (drain tests, operators)
                        # and must not grow required kwargs under them
                        if tenant is None:
                            result = self.handle_query(data, deadline)
                        else:
                            result = self.handle_query(
                                data, deadline,
                                tenant=tenant, variant=variant,
                            )
                        return json_response(200, result)
                except DeadlineExceeded as e:
                    return json_response(504, {"message": str(e)})
                except TypeError as e:
                    return json_response(400, {"message": str(e)})
            finally:
                with self._inflight_lock:
                    self._inflight -= 1

        @svc.route("POST", r"/queries\.json")
        def queries(req: Request):
            with _tracing.stage("decode"):
                data = req.json()
            if not isinstance(data, dict):
                return json_response(400, {"message": "query must be a JSON object"})
            if self._draining:
                # draining: in-flight work finishes, new work goes elsewhere
                return Response(
                    status=503,
                    body={"message": "server draining; retry against "
                          "another instance"},
                    headers={"Retry-After": f"{self.retry_after_s():g}"},
                )
            if self._pod_lockstep():
                # refusing beats deadlocking: one process of a
                # process-spanning pod mesh cannot dispatch alone — its
                # SPMD peers would never join the cross-host collective
                return Response(
                    status=503,
                    body={"message": "pod mesh spans processes: queries "
                          "must be dispatched in SPMD lockstep on every "
                          "process, not routed to one — serve through "
                          "self-contained host-local replicas instead"},
                    headers={"Retry-After": f"{self.retry_after_s():g}"},
                )
            if _faults.active() is not None:
                # generation-keyed chaos: a rule on server:generation:<id>
                # degrades ONLY the replica serving that engine instance —
                # how the canary bench injects a bad candidate generation
                # without touching its baseline siblings in the same image
                with self._lock:
                    live = self._deployed
                if live is not None:
                    act = _faults.check(
                        f"server:generation:{live.instance_id}"
                    )
                    if act is not None:
                        if act.latency_s:
                            time.sleep(act.latency_s)
                        if act.kind in ("error", "drop", "crash"):
                            return json_response(
                                act.status or 500,
                                {"message": "injected generation fault",
                                 "injected": True},
                            )
            reg = self._tenants
            if reg is None:
                return _serve_admitted(req, data, None, None)
            # multi-tenant surface: the event-server auth contract on the
            # query plane — key from ?accessKey=, X-PIO-Access-Key, or the
            # body's accessKey field (stripped from cache fingerprints)
            key = extract_access_key(req.params, req.headers, data)
            if not key:
                return json_response(401, {"message": "Missing accessKey."})
            spec = reg.authenticate(key)
            if spec is None:
                return json_response(401, {"message": "Invalid accessKey."})
            tenant = spec.tenant_id
            act = _faults.check(f"client:tenant:{tenant}")
            if act is not None:
                # a chaos-injected bad request FROM this tenant: it feeds
                # this tenant's breaker only — the isolation contract the
                # chaos suite asserts on every other tenant's breaker
                if act.latency_s:
                    time.sleep(act.latency_s)
                if act.kind in ("error", "drop", "crash"):
                    reg.record_result(tenant, None, ok=False, latency_s=0.0)
                    return json_response(
                        act.status or 503,
                        {"message": "injected fault", "injected": True},
                    )
            adm = reg.admit(tenant)
            if not adm.ok:
                # per-tenant shed: quota exhausted, fair-share inflight
                # cap, or this tenant's breaker open — 503 with a
                # quota-aware Retry-After, never touching other tenants
                return Response(
                    status=503,
                    body={"message": f"tenant {tenant} shed", "tenant": tenant,
                          "reason": adm.reason},
                    headers={"Retry-After": f"{adm.retry_after_s:g}"},
                )
            variant = reg.pick_variant(tenant, data.get("user"))
            ok = False
            t0 = time.perf_counter()
            try:
                resp = _serve_admitted(req, data, tenant, variant)
                # 4xx/503 are the contract working, not tenant failures;
                # only 5xx server errors feed this tenant's breaker
                ok = resp.status < 500 or resp.status == 503
                return resp
            finally:
                reg.release(tenant)
                reg.record_result(
                    tenant, variant, ok=ok,
                    latency_s=time.perf_counter() - t0,
                )

        @svc.route("GET", r"/reload")
        @svc.route("POST", r"/reload")
        def reload_route(req: Request):
            # ?instanceId= pins the swap to one generation (the canary
            # controller's promote/rollback hop); quarantined ids refuse
            # with 409 unless ?force=1 (operator override)
            target = (req.params.get("instanceId") or "").strip() or None
            force = (req.params.get("force") or "") in ("1", "true", "yes")
            try:
                iid = self.reload(instance_id=target, force=force)
            except RuntimeError as e:
                if "quarantined" in str(e):
                    return json_response(409, {"message": str(e)})
                raise
            return json_response(200, {"message": "Reloaded", "engineInstanceId": iid})

        @svc.route("POST", r"/delta")
        def delta_route(req: Request):
            # router → replica delta hop: body is the sealed checksum
            # envelope, verbatim.  Every answer is a receipt the router
            # records as this replica's apply acknowledgement.  A torn or
            # forged payload is an integrity REFUSAL (200 + receipt), not
            # a 5xx — the replica keeps serving its last good epoch.
            st = self._streaming
            if st is None:
                return json_response(
                    409,
                    {"refused": True, "reason": "streaming disabled",
                     "streaming": _delta.streaming_enabled()},
                )
            try:
                payload = open_model_blob(req.body)
                dl = _delta.Delta.from_payload(payload)
            except Exception as e:
                # legacy passthrough means garbage survives the envelope
                # check and dies at unpickle — either way it never reaches
                # the factors
                receipt = st["applier"].refuse("integrity", error=str(e))
                return json_response(200, receipt)
            receipt = st["applier"].apply(dl)
            if receipt.get("applied"):
                st["wedged"] = None
            return json_response(200, receipt)

        @svc.route("GET", r"/delta/stats")
        def delta_stats_route(req: Request):
            stats = self.streaming_stats()
            if stats is None:
                return json_response(
                    404, {"message": "streaming disabled"}
                )
            return json_response(200, stats)

        @svc.route("POST", r"/stop")
        def stop_route(req: Request):
            def _stop():
                time.sleep(0.3)  # let the response flush before the socket dies
                self.drain()

            threading.Thread(target=_stop, daemon=True).start()
            return json_response(200, {"message": "Shutting down."})

        @svc.route("GET", r"/trace/dispatches\.json")
        def dispatches_route(req: Request):
            # one record per batch run, kept by the batcher whether or not
            # a request was sampled (docs/observability.md)
            if self._batcher is None:
                return json_response(
                    404, {"message": "batching disabled"}
                )
            try:
                limit = int(req.params.get("limit") or 0) or None
            except (TypeError, ValueError):
                return json_response(
                    400, {"message": "limit must be an integer"}
                )
            return json_response(200, self._batcher.dispatches(limit))

        @svc.route("POST", r"/debug/profile")
        def profile_route(req: Request):
            # guarded, bounded, single-flight: jax.profiler is process-
            # global, so concurrent captures are refused (409) rather
            # than interleaved; the window is capped so a fat-fingered
            # ms can't hold the trace machinery open for minutes
            if os.environ.get("PIO_PROFILE_ENDPOINT", "1") == "0":
                return json_response(
                    403,
                    {"message": "profile endpoint disabled "
                     "(PIO_PROFILE_ENDPOINT=0)"},
                )
            try:
                ms = int(req.params.get("ms") or 500)
            except (TypeError, ValueError):
                return json_response(
                    400, {"message": "ms must be an integer"}
                )
            ms = max(1, min(ms, 10_000))
            if not self._profile_lock.acquire(blocking=False):
                return json_response(
                    409, {"message": "a profile capture is already running"}
                )
            try:
                path = _devprof.capture_profile(ms)
            except Exception as e:
                self._rl_log.exception(
                    "profile", "profile capture failed"
                )
                return json_response(
                    500, {"message": f"profile capture failed: {e}"}
                )
            finally:
                self._profile_lock.release()
            with self._lock:
                self._profile_captures += 1
                self._profile_last_unix = time.time()
            return json_response(200, {"path": path, "ms": ms})

        @svc.route("GET", r"/plugins\.json")
        def plugins_route(req: Request):
            return json_response(
                200,
                {
                    "plugins": {
                        "outputblockers": {
                            p.name: {"class": type(p).__name__}
                            for p in self.plugins
                            if p.plugin_type == EngineServerPlugin.OUTPUT_BLOCKER
                        },
                        "outputsniffers": {
                            p.name: {"class": type(p).__name__}
                            for p in self.plugins
                            if p.plugin_type == EngineServerPlugin.OUTPUT_SNIFFER
                        },
                    }
                },
            )

    # -- lifecycle ---------------------------------------------------------------
    def start(self, host: str = "0.0.0.0", port: int = 8000, **tls) -> int:
        actual = self.service.start(host, port, **tls)
        logger.info("query server listening on %s:%s", host, actual)
        return actual

    def drain(self, timeout_ms: Optional[float] = None) -> bool:
        """Graceful shutdown: flip /readyz to draining (new queries shed),
        wait for in-flight queries — including queued micro-batches — to
        finish inside the budget, then stop. Returns True when nothing
        was abandoned; abandoned work is counted either way."""
        budget_s = (
            timeout_ms if timeout_ms is not None else self.drain_timeout_ms
        ) / 1e3
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + max(budget_s, 0.0)
        while time.monotonic() < deadline:
            with self._inflight_lock:
                inflight = self._inflight
            if inflight == 0:
                break
            time.sleep(0.005)
        with self._inflight_lock:
            abandoned = self._inflight
        if abandoned:
            self.counters.inc("drain_abandoned", abandoned)
            logger.warning(
                "drain budget (%.0fms) lapsed with %d queries in flight",
                budget_s * 1e3, abandoned,
            )
        else:
            self.counters.inc("drained")
        self.stop()
        return abandoned == 0

    def stop(self) -> None:
        self._stop_streaming()
        if self._batcher is not None:
            self._batcher.stop()
        if self._feedback_worker is not None:
            try:
                self._feedback_queue.put_nowait(None)  # drain-and-exit sentinel
            except queue.Full:
                pass  # worker is wedged; it's a daemon thread, let it die
        self.service.stop()
