"""Unified telemetry: registry, exposition round-trip, cross-layer traces.

Acceptance for the obs subsystem: ``/metrics`` on both servers carries
≥25 named series in valid Prometheus text (proved by a strict parser
round-trip), a header-forced query trace shows all six stages
(decode → queue_wait → batch_assembly → h2d → device_compute →
serialize) non-negative and summing to the wall, and the AOT warmup
satellite holds zero-compile-under-traffic.
"""

import json
import math
import time
import urllib.error
import urllib.request
import uuid

import numpy as np
import pytest

from predictionio_tpu import obs
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data import Event
from predictionio_tpu.data import store as store_mod
from predictionio_tpu.data.api.event_server import EventServer
from predictionio_tpu.data.api.stats import OVERFLOW_EVENT, Stats
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.obs import metrics as obs_metrics
from predictionio_tpu.obs import tracing as obs_tracing
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.query_server import QueryServer
from predictionio_tpu.templates.recommendation import RecommendationEngine


# -- registry units -----------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("pio_c_total", "c")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("pio_g", "g")
        g.set(5)
        g.dec(2)
        assert g.value == 3
        h = reg.histogram("pio_h_seconds", "h", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(100)
        text = reg.render_prometheus()
        series = obs_metrics.parse_prometheus(text)
        assert series[("pio_h_seconds_bucket", (("le", "0.1"),))] == 1
        assert series[("pio_h_seconds_bucket", (("le", "1"),))] == 2
        assert series[("pio_h_seconds_bucket", (("le", "+Inf"),))] == 3
        assert series[("pio_h_seconds_count", ())] == 3

    def test_get_or_create_and_kind_mismatch(self):
        reg = obs_metrics.MetricsRegistry()
        a = reg.counter("pio_x_total", "x")
        assert reg.counter("pio_x_total", "x") is a
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("pio_x_total", "x")

    def test_labels_and_cardinality_overflow(self):
        reg = obs_metrics.MetricsRegistry()
        c = obs_metrics.Counter("pio_l_total", "l", ("k",), max_series=3)
        for i in range(10):
            c.labels(f"v{i}").inc()
        fam = c.collect()
        label_sets = {labels for _, labels, _ in fam.samples}
        # 3 real children + ONE shared overflow series, never 10
        assert len(label_sets) == 4
        overflow = dict(
            (labels, v) for _, labels, v in fam.samples
        )[(("k", obs_metrics.OVERFLOW_LABEL),)]
        assert overflow == 7

    def test_label_count_mismatch_raises(self):
        c = obs_metrics.Counter("pio_m_total", "m", ("a", "b"))
        with pytest.raises(ValueError, match="label"):
            c.labels("only-one")

    def test_invalid_names_rejected(self):
        with pytest.raises(ValueError):
            obs_metrics.Counter("2bad", "x")
        with pytest.raises(ValueError):
            obs_metrics.Counter("pio_ok_total", "x", ("bad-label",))


class TestExpositionRoundTrip:
    def test_round_trip_preserves_every_series(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("pio_rt_total", "rt", ("method", "status"))
        c.labels("GET", "200").inc(7)
        c.labels("POST", "201").inc(1)
        g = reg.gauge("pio_rt_g", "g")
        g.set(2.5)
        h = reg.histogram("pio_rt_seconds", "h")
        for v in (0.001, 0.004, 0.2):
            h.observe(v)
        text = reg.render_prometheus()
        series = obs_metrics.parse_prometheus(text)
        assert series[
            ("pio_rt_total", (("method", "GET"), ("status", "200")))
        ] == 7
        assert series[("pio_rt_g", ())] == 2.5
        assert series[("pio_rt_seconds_count", ())] == 3
        assert series[("pio_rt_seconds_sum", ())] == pytest.approx(0.205)
        # the JSON exposition carries the same families
        j = reg.render_json()
        assert {m["name"] for m in j["metrics"]} == {
            "pio_rt_total", "pio_rt_g", "pio_rt_seconds"
        }
        json.dumps(j)  # and is actually serializable

    def test_label_escaping_round_trips(self):
        reg = obs_metrics.MetricsRegistry()
        c = reg.counter("pio_esc_total", "e", ("p",))
        nasty = 'sla\\sh "quote"\nnewline'
        c.labels(nasty).inc()
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        assert series[("pio_esc_total", (("p", nasty),))] == 1

    def test_parser_rejects_malformed_and_duplicates(self):
        with pytest.raises(ValueError, match="malformed"):
            obs_metrics.parse_prometheus("not a metric line!\n")
        with pytest.raises(ValueError, match="duplicate"):
            obs_metrics.parse_prometheus("pio_a 1\npio_a 2\n")

    def test_special_values(self):
        reg = obs_metrics.MetricsRegistry()
        reg.gauge_fn("pio_nan", "n", lambda: float("nan"))
        reg.gauge_fn("pio_inf", "i", lambda: math.inf)
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        assert series[("pio_nan", ())] != series[("pio_nan", ())]  # NaN
        assert series[("pio_inf", ())] == math.inf

    def test_broken_collector_never_breaks_exposition(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("pio_ok_total", "ok").inc()
        reg.register_collector(lambda: 1 / 0)
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        assert series[("pio_ok_total", ())] == 1


# -- tracer units -------------------------------------------------------------


class TestTracer:
    def test_deterministic_every_nth_sampling(self):
        t = obs_tracing.Tracer(sample_rate=0.25, ring_size=8)
        decisions = [t.begin(None, "q") is not None for _ in range(20)]
        assert sum(decisions) == 5  # exactly rate * n, no RNG
        assert decisions == [False, False, False, True] * 5

    def test_header_forces_sampling_at_rate_zero(self):
        t = obs_tracing.Tracer(sample_rate=0.0, ring_size=8)
        assert t.begin(None, "q") is None
        tr = t.begin("abc123", "q")
        assert tr is not None and tr.request_id == "abc123"

    def test_stage_sum_equals_wall(self):
        t = obs_tracing.Tracer(sample_rate=1.0, ring_size=8)
        tr = t.begin(None, "q")
        with tr.stage("decode"):
            time.sleep(0.002)
        tr.finish(200)
        d = tr.to_dict()
        assert d["stagesMs"]["decode"] >= 0
        assert d["stagesMs"]["other"] >= 0
        assert sum(d["stagesMs"].values()) == pytest.approx(
            d["wallMs"], abs=0.01
        )

    def test_ring_is_bounded_newest_first(self):
        t = obs_tracing.Tracer(sample_rate=1.0, ring_size=3)
        for i in range(5):
            tr = t.begin(f"id{i}", "q")
            tr.finish(200)
            t.record(tr)
        recent = t.recent()
        assert [r["requestId"] for r in recent] == ["id4", "id3", "id2"]

    def test_scope_charges_all_active_traces(self):
        t = obs_tracing.Tracer(sample_rate=1.0, ring_size=8)
        a, b = t.begin("a" * 6, "q"), t.begin("b" * 6, "q")
        with obs_tracing.scope((a, b)):
            with obs_tracing.stage("h2d"):
                pass
        assert "h2d" in a.stages and "h2d" in b.stages

    def test_stage_noop_without_scope(self):
        # must not raise, must not allocate a trace
        with obs_tracing.stage("device_compute"):
            pass
        assert obs_tracing.active_traces() == ()


class TestRequestPresence:
    """ISSUE 37: a request is on the profiler's clock from its request line
    to its last byte, and the time before its trace is born rides on the
    trace as ``meta.parse_ms`` without moving its wall or its stages."""

    @pytest.fixture()
    def service(self, monkeypatch):
        import types

        from predictionio_tpu.common import http as http_mod

        class Ticks:
            """perf_counter that moves 1 ms each time it is read."""

            def __init__(self):
                self.t = 50.0

            def __call__(self):
                self.t += 0.001
                return self.t

        shim = types.SimpleNamespace(perf_counter=Ticks(), time=time.time,
                                     sleep=time.sleep)
        monkeypatch.setattr(http_mod, "time", shim)
        monkeypatch.setattr(obs_tracing, "time", shim)
        seen = []
        monkeypatch.setattr(
            obs_tracing, "annotation",
            lambda name, **kv: seen.append((name, kv)) or obs_tracing._NO_SPAN)
        born = []
        real_init = obs_tracing.Trace.__init__

        def counted(self, *a, **kw):
            born.append(a)
            real_init(self, *a, **kw)

        monkeypatch.setattr(obs_tracing.Trace, "__init__", counted)
        svc = http_mod.HttpService("presence")
        tel = obs.Telemetry("presence", sample_rate=0.0).install(svc)

        @svc.route("POST", r"/echo\.json")
        def _echo(req):
            return http_mod.json_response(200, req.json())

        port = svc.start("127.0.0.1", 0)
        yield {"base": f"http://127.0.0.1:{port}", "tel": tel, "seen": seen,
               "born": born}
        svc.stop()

    def test_a_sampled_request_carries_parse_ms_outside_its_wall(
            self, service):
        _post(service["base"] + "/echo.json", {"a": 1},
              headers={obs.TRACE_HEADER: "presence-1"})
        ring, end = [], time.monotonic() + 5.0
        while not ring and time.monotonic() < end:
            ring = service["tel"].tracer.recent()
            time.sleep(0.01)
        (d,) = ring
        # clock reads of the request, 1 ms apart: parse start | t_req |
        # trace born | send start | send end | finish.  The first is new;
        # from the trace's birth on they are the reads there were
        assert d["meta"] == {"parse_ms": 1.0}
        assert d["wallMs"] == 3.0
        assert d["stagesMs"] == {"serialize": 1.0, "other": 2.0}

    def test_presence_spans_open_in_order_with_the_requests_id(
            self, service):
        _post(service["base"] + "/echo.json", {"a": 1},
              headers={obs.TRACE_HEADER: "presence-2"})
        assert service["seen"][:2] == [
            ("pio_req.parse", {}), ("pio_req.handle", {"id": "presence-2"})]

    def test_an_unsampled_request_allocates_no_trace(self, service):
        for _ in range(3):
            _post(service["base"] + "/echo.json", {"a": 1})
        tracer = service["tel"].tracer
        assert (tracer.seen, tracer.sampled) == (3, 0)
        assert service["born"] == [] and len(tracer.ring) == 0
        # presence is still marked (a flag test without a session), with no
        # id to give
        assert service["seen"].count(("pio_req.handle", {"id": ""})) == 3
        assert all(not n.startswith("pio.") for n, _ in service["seen"])


# -- Stats cardinality cap ----------------------------------------------------


class TestStatsCap:
    def test_overflow_bucket_caps_hostile_event_names(self):
        s = Stats(max_keys=3)
        for i in range(10):
            s.update(1, f"hostile{i}", 201)
        counts = s.snapshot_all()[1]
        assert len(counts) <= 4  # 3 real + the overflow key
        assert counts[(OVERFLOW_EVENT, 201)] == 7
        total = sum(counts.values())
        assert total == 10  # totals stay truthful

    def test_get_all_shape(self):
        s = Stats()
        s.update(1, "rate", 201)
        s.update(2, "buy", 400)
        out = s.get_all()
        assert set(out["apps"]) == {"1", "2"}
        assert out["apps"]["1"][0] == {
            "event": "rate", "status": 201, "count": 1
        }


# -- live servers -------------------------------------------------------------


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req) as r:
        return r.status, r.read(), r.headers


def _post(url, body, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), headers=hdrs
    )
    with urllib.request.urlopen(req) as r:
        return r.status, r.read(), r.headers


def _scrape(base, min_series=1, deadline_s=5.0):
    """Parse /metrics, retrying briefly: request accounting lands just
    AFTER the response bytes, so an immediate scrape can race it."""
    end = time.monotonic() + deadline_s
    while True:
        _, body, headers = _get(base + "/metrics")
        assert headers["Content-Type"].startswith("text/plain")
        series = obs_metrics.parse_prometheus(body.decode())
        if len(series) >= min_series or time.monotonic() > end:
            return series
        time.sleep(0.02)


@pytest.fixture()
def trained(storage):
    store_mod.set_storage(storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "obsapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(12)
    le.batch_insert(
        [
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties={"rating": float(rng.integers(1, 6))})
            for u in range(10)
            for i in rng.choice(10, size=4, replace=False)
        ],
        app_id,
    )
    engine = RecommendationEngine.apply()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "obsapp"}},
        "algorithms": [
            {"name": "als", "params": {"rank": 2, "numIterations": 2}}
        ],
    })
    ctx = MeshContext.create()
    run_train(engine, ep, "obs", storage=storage, ctx=ctx)
    yield {"storage": storage, "engine": engine, "ctx": ctx,
           "app_id": app_id}
    store_mod.set_storage(None)


class TestQueryServerTelemetry:
    def _server(self, trained, **kw):
        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"], **kw,
        )
        port = qs.start("127.0.0.1", 0)
        return qs, f"http://127.0.0.1:{port}"

    def test_metrics_has_25_series_and_parses(self, trained):
        qs, base = self._server(trained, batching=True)
        try:
            for i in range(4):
                _post(base + "/queries.json", {"user": f"u{i}", "num": 3})
            series = _scrape(base, min_series=25)
            names = {n for n, _ in series}
            assert len(series) >= 25, sorted(names)
            # the migrated stat families are all present
            for expected in (
                "pio_http_requests_total",
                "pio_query_requests_total",
                "pio_query_latency_seconds_bucket",
                "pio_query_errors_total",
                "pio_batcher_queries_total",
                "pio_fastpath_compiles_total",
                "pio_server_info",
            ):
                assert expected in names, expected
            assert series[
                ("pio_server_info", (("service", "queryserver"),))
            ] == 1
            # JSON exposition of the same registry
            _, body, _ = _get(base + "/metrics?format=json")
            j = json.loads(body.decode())
            assert {m["name"] for m in j["metrics"]} >= {
                "pio_http_requests_total", "pio_query_requests_total"
            }
        finally:
            qs.stop()

    def test_forced_trace_has_all_six_stages_summing_to_wall(self, trained):
        qs, base = self._server(trained, batching=True)
        try:
            _post(base + "/queries.json", {"user": "u1", "num": 3})  # warm
            rid = uuid.uuid4().hex[:16]
            _, _, headers = _post(
                base + "/queries.json", {"user": "u2", "num": 3},
                headers={obs.TRACE_HEADER: rid},
            )
            assert headers.get(obs.TRACE_HEADER) == rid  # echoed back
            # the trace lands in the ring just AFTER the response bytes, so
            # poll briefly instead of racing it
            mine, deadline = [], time.monotonic() + 5.0
            while not mine and time.monotonic() < deadline:
                _, body, _ = _get(base + "/trace/recent.json")
                doc = json.loads(body.decode())
                assert doc["service"] == "queryserver"
                mine = [t for t in doc["traces"] if t["requestId"] == rid]
                if not mine:
                    time.sleep(0.02)
            assert mine, doc["traces"]
            tr = mine[0]
            need = {"decode", "queue_wait", "batch_assembly", "h2d",
                    "device_compute", "serialize"}
            assert need <= set(tr["stagesMs"]), tr["stagesMs"]
            assert all(v >= 0 for v in tr["stagesMs"].values())
            assert sum(tr["stagesMs"].values()) == pytest.approx(
                tr["wallMs"], abs=0.05
            )
        finally:
            qs.stop()

    def test_unforced_request_gets_generated_id(self, trained):
        qs, base = self._server(trained)
        try:
            # sample_rate dictates ring admission, but EVERY telemetry
            # response that was sampled echoes an id; force via header-less
            # deterministic sampler at rate 1.0
            qs.telemetry.tracer.sample_rate = 1.0
            qs.telemetry.tracer._acc = 0.0
            _, _, headers = _post(
                base + "/queries.json", {"user": "u1", "num": 2}
            )
            rid = headers.get(obs.TRACE_HEADER)
            assert rid and len(rid) == 16
        finally:
            qs.stop()

    def test_warmup_zero_compiles_under_traffic(self, trained):
        """The AOT warmup satellite: with batching on, the bucket ladder
        compiles at deploy; traffic afterwards must never compile."""
        qs, base = self._server(trained, batching=True)
        try:
            compiles_at_deploy = qs._fastpath_stats()["compile_count"]
            assert compiles_at_deploy > 0  # warmup actually ran
            for i in range(12):
                _post(base + "/queries.json", {"user": f"u{i % 10}",
                                               "num": 3})
            stats = qs._fastpath_stats()
            assert stats["compile_count"] == compiles_at_deploy
            assert stats["calls"] > 0  # traffic really hit the fastpath
            series = _scrape(base)
            assert series[
                ("pio_fastpath_compiles_total", ())
            ] == compiles_at_deploy
        finally:
            qs.stop()

    def test_telemetry_off_means_no_routes_no_overhead_hooks(self, trained):
        qs, base = self._server(trained, telemetry=False)
        try:
            assert qs.telemetry is None and qs.service.telemetry is None
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/metrics")
            assert ei.value.code == 404
            status, _, headers = _post(
                base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200
            assert headers.get(obs.TRACE_HEADER) is None
        finally:
            qs.stop()

    def test_kill_switch_env(self, trained, monkeypatch):
        monkeypatch.setenv("PIO_TELEMETRY", "0")
        qs, base = self._server(trained)
        try:
            assert qs.telemetry is None
        finally:
            qs.stop()


class TestDeviceProfilerAndFlightRecorder:
    """ISSUE 8 acceptance at the server level: live ``pio_device_*``
    gauges under traffic, stage-annotated slow exemplars at
    ``/trace/slow.json``, a readable ``POST /debug/profile`` capture, and
    the charge-once invariant for result-cache hits."""

    def _server(self, trained, **kw):
        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"], **kw,
        )
        port = qs.start("127.0.0.1", 0)
        return qs, f"http://127.0.0.1:{port}"

    def test_device_gauges_nonnull_nonzero_under_traffic(self, trained):
        qs, base = self._server(trained, batching=True)
        try:
            for i in range(8):
                _post(base + "/queries.json", {"user": f"u{i}", "num": 3})
            series = _scrape(base, min_series=25)
            gen = (("generation", str(qs._serving_gen)),)
            busy = series[("pio_device_busy_fraction", gen)]
            assert 0.0 < busy <= 1.0
            assert series[("pio_device_flops_per_s", gen)] > 0
            assert series[("pio_device_hbm_gbps", gen)] > 0
            assert series[("pio_device_dispatches_total", gen)] >= 1
            assert series[("pio_device_busy_seconds", gen)] > 0
            # the peak table is keyed by device_kind and has no CPU row:
            # a CPU run must not print a utilization under a device
            # metric's name
            assert ("pio_device_mfu", gen) not in series
            assert ("pio_device_hbm_util", gen) not in series
            # fastpath stats carry the same snapshot + the cost sources
            dev = qs._fastpath_stats()["devprof"]
            assert dev["dispatches_total"] >= 1
            d = qs._deployed
            scorer = d.algorithms[0]._scorers[id(d.models[0])]
            costs = scorer._fastpath.devprof.costs()
            assert costs  # every bucket annotated at compile time
            assert all(
                c["source"] in ("xla", "analytic") and c["flops"] > 0
                for c in costs.values()
            )
        finally:
            qs.stop()

    def test_slow_json_stage_annotated_exemplars(self, trained):
        qs, base = self._server(trained, batching=True)
        try:
            # every request sampled, median threshold: outliers are just
            # the slower half of natural jitter — no timing games needed
            qs.telemetry.tracer.sample_rate = 1.0
            qs.telemetry.tracer._acc = 0.0
            qs.telemetry.tracer.slow_quantile = 0.5
            for i in range(48):
                _post(base + "/queries.json",
                      {"user": f"u{i % 10}", "num": 3})
            doc, deadline = None, time.monotonic() + 5.0
            while time.monotonic() < deadline:
                _, body, _ = _get(base + "/trace/slow.json?limit=10")
                doc = json.loads(body.decode())
                if doc["retained"] > 0:
                    break
                time.sleep(0.02)
            assert doc["service"] == "queryserver"
            assert doc["quantile"] == 0.5
            assert doc["retained"] > 0, doc
            assert doc["thresholdMs"] is not None
            assert doc["traces"], doc
            for tr in doc["traces"]:
                # an exemplar explains itself: full stage breakdown that
                # reconciles with the wall
                assert tr["wallMs"] is not None
                assert "other" in tr["stagesMs"]
                assert sum(tr["stagesMs"].values()) == pytest.approx(
                    tr["wallMs"], abs=0.05
                )
            # at rate 1.0 the ring may also hold slow scrape GETs; the
            # QUERY exemplars must carry the batch context
            queries = [t for t in doc["traces"]
                       if "queries" in t.get("name", "")]
            assert queries, doc["traces"]
            for tr in queries:
                assert "batch" in tr.get("meta", {}), tr
            # recorder health is on /metrics too
            series = _scrape(base)
            assert series[("pio_slow_trace_retained", ())] > 0
            assert series[("pio_slow_trace_threshold_seconds", ())] > 0
        finally:
            qs.stop()

    def test_debug_profile_writes_readable_trace(
        self, trained, tmp_path, monkeypatch
    ):
        import os

        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
        qs, base = self._server(trained, batching=True)
        try:
            status, body, _ = _post(base + "/debug/profile?ms=30", {})
            assert status == 200
            doc = json.loads(body.decode())
            assert doc["ms"] == 30
            assert doc["path"].startswith(str(tmp_path))
            captured = [
                os.path.join(root, f)
                for root, _, files in os.walk(doc["path"])
                for f in files
            ]
            assert captured, f"empty profile dir {doc['path']}"
            assert any(os.path.getsize(p) > 0 for p in captured)
            series = _scrape(base)
            assert series[("pio_profile_captures_total", ())] == 1
            assert series[("pio_profile_last_capture_unix", ())] > 0
        finally:
            qs.stop()

    def test_debug_profile_rejects_bad_ms_and_honors_kill_switch(
        self, trained, monkeypatch
    ):
        qs, base = self._server(trained)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/debug/profile?ms=banana", {})
            assert ei.value.code == 400
            monkeypatch.setenv("PIO_PROFILE_ENDPOINT", "0")
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(base + "/debug/profile?ms=10", {})
            assert ei.value.code == 403
        finally:
            qs.stop()

    def test_cache_hit_trace_has_no_device_stages(self, trained):
        """Satellite: device time is charged once per dispatch — a
        result-cache hit never reaches the device, and its trace must say
        so while still reconciling stage sum ≡ wall."""
        from predictionio_tpu.serving.result_cache import ResultCache

        qs, base = self._server(
            trained, batching=True, result_cache=ResultCache()
        )
        try:
            q = {"user": "u1", "num": 3}
            _post(base + "/queries.json", q)  # fill the cache
            before = qs._fastpath_stats()["devprof"]["dispatches_total"]
            rid = uuid.uuid4().hex[:16]
            _post(base + "/queries.json", q,
                  headers={obs.TRACE_HEADER: rid})
            mine, deadline = [], time.monotonic() + 5.0
            while not mine and time.monotonic() < deadline:
                _, body, _ = _get(base + "/trace/recent.json")
                doc = json.loads(body.decode())
                mine = [t for t in doc["traces"]
                        if t["requestId"] == rid]
                if not mine:
                    time.sleep(0.02)
            assert mine, doc["traces"]
            tr = mine[0]
            assert tr["meta"]["cache"] == "hit", tr
            for stage in ("device_compute", "h2d", "batch_assembly",
                          "queue_wait"):
                assert stage not in tr["stagesMs"], tr
            assert sum(tr["stagesMs"].values()) == pytest.approx(
                tr["wallMs"], abs=0.05
            )
            # and the accountant never saw a dispatch for the hit
            after = qs._fastpath_stats()["devprof"]["dispatches_total"]
            assert after == before
        finally:
            qs.stop()


class TestEventServerTelemetry:
    @pytest.fixture()
    def served(self, storage):
        store_mod.set_storage(storage)
        app_id = storage.get_meta_data_apps().insert(App(0, "evapp"))
        key = storage.get_meta_data_access_keys().insert(
            AccessKey("", app_id, [])
        )
        es = EventServer(storage=storage, stats=True)
        port = es.start("127.0.0.1", 0)
        yield {"es": es, "base": f"http://127.0.0.1:{port}",
               "key": key, "app_id": app_id}
        es.stop()
        store_mod.set_storage(None)

    def _ingest(self, served, n=3):
        for i in range(n):
            _post(
                served["base"] + f"/events.json?accessKey={served['key']}",
                {"event": "rate", "entityType": "user",
                 "entityId": f"u{i}", "targetEntityType": "item",
                 "targetEntityId": f"i{i}", "properties": {"rating": 5}},
            )

    def test_metrics_has_25_series_and_ingest_counts(self, served):
        self._ingest(served)
        series = _scrape(served["base"], min_series=25)
        assert len(series) >= 25, sorted({n for n, _ in series})
        assert series[
            (
                "pio_events_ingested_total",
                (
                    ("app_id", str(served["app_id"])),
                    ("event", "rate"),
                    ("status", "201"),
                ),
            )
        ] == 3
        assert series[("pio_stats_enabled", ())] == 1
        assert series[
            ("pio_server_info", (("service", "eventserver"),))
        ] == 1

    def test_stats_json_all_apps_without_key(self, served):
        self._ingest(served, n=2)
        _, body, _ = _get(served["base"] + "/stats.json")
        doc = json.loads(body.decode())
        counts = doc["apps"][str(served["app_id"])]
        assert counts[0]["event"] == "rate" and counts[0]["count"] == 2

    def test_stats_json_per_app_with_key(self, served):
        self._ingest(served, n=1)
        _, body, _ = _get(
            served["base"] + f"/stats.json?accessKey={served['key']}"
        )
        doc = json.loads(body.decode())
        assert doc["statusCount"][0]["event"] == "rate"


class TestCrossServiceTracePropagation:
    def test_storage_client_carries_request_id(self, mem_env):
        """A traced request that touches the network storage client must
        land in the STORAGE server's trace ring under the same id."""
        from predictionio_tpu.data.storage.network import StorageServer
        from predictionio_tpu.data.storage.registry import Storage

        backing = Storage(env=mem_env)
        server = StorageServer(backing, secret="s3cret")
        port = server.start("127.0.0.1", 0)
        client = Storage(env={
            "PIO_STORAGE_SOURCES_NET_TYPE": "network",
            "PIO_STORAGE_SOURCES_NET_URL": f"http://127.0.0.1:{port}",
            "PIO_STORAGE_SOURCES_NET_SECRET": "s3cret",
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "NET",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NET",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "NET",
        })
        try:
            tracer = obs_tracing.Tracer(sample_rate=1.0, ring_size=8)
            tr = tracer.begin("feedbeef0badcafe", "POST /queries.json")
            with obs_tracing.scope((tr,)):
                client.get_meta_data_apps().get_all()
            ids, deadline = set(), time.monotonic() + 5.0
            while "feedbeef0badcafe" not in ids and (
                time.monotonic() < deadline
            ):
                _, body, _ = _get(
                    f"http://127.0.0.1:{port}/trace/recent.json"
                )
                doc = json.loads(body.decode())
                assert doc["service"] == "storageserver"
                ids = {t["requestId"] for t in doc["traces"]}
                if "feedbeef0badcafe" not in ids:
                    time.sleep(0.02)
            assert "feedbeef0badcafe" in ids, doc["traces"]
        finally:
            server.stop()


class TestLoadtestScrape:
    def test_scrape_and_summarize(self, trained):
        from predictionio_tpu.tools.loadtest import (
            run_loadtest,
            scrape_metrics,
            summarize_metrics,
        )

        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"], batching=True,
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            res = run_loadtest(base, {"user": "u1", "num": 3},
                               requests=8, concurrency=2)
            assert res["errors"] == 0
            # the HTTP layer counts a request after its last byte is sent,
            # so the 8th increment can trail the client's return briefly
            deadline = time.monotonic() + 5.0
            while True:
                summary = summarize_metrics(scrape_metrics(base))
                if summary["httpRequests"] >= 8 or time.monotonic() > deadline:
                    break
                time.sleep(0.05)
            assert summary["seriesCount"] >= 25
            assert summary["httpRequests"] >= 8
            assert summary["batcherQueries"] >= 8
        finally:
            qs.stop()
