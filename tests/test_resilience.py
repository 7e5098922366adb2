"""Chaos suite: seeded fault plans against the real service planes.

Every test here injects faults through ``common/faults.py`` (or drives the
resilience primitives directly) and asserts the behavior the resilience
layer promises: retries recover transient faults, breakers fail fast and
heal, deadlines shed work before it reaches the device, and a broken
scorer degrades instead of 500ing.  Plans are SEEDED — the same test run
replays the same fault schedule every time.
"""

import json
import threading
import time
import urllib.error
import urllib.request
import uuid

import numpy as np
import pytest

from predictionio_tpu.common import faults
from predictionio_tpu.common.resilience import (
    BreakerOpen,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    RetryBudget,
    RetryPolicy,
    call_with_resilience,
    parse_deadline_header,
)
from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data import Event
from predictionio_tpu.data import store as store_mod
from predictionio_tpu.data.storage import App
from predictionio_tpu.data.storage.network import (
    NetworkStorageError,
    StorageServer,
)
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.batching import MicroBatcher
from predictionio_tpu.serving.query_server import QueryServer
from predictionio_tpu.templates.recommendation import RecommendationEngine

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    faults.clear()
    yield
    faults.clear()


def _rule(**kw):
    return faults.FaultRule(**kw)


# -- determinism of the harness itself ---------------------------------------


class TestFaultPlanDeterminism:
    def test_same_seed_same_schedule(self):
        def schedule(seed):
            plan = faults.FaultPlan(
                [_rule(site="s:*", kind="error", p=0.4)], seed=seed
            )
            return [plan.on_call("s:x") is not None for _ in range(50)]

        a, b = schedule(7), schedule(7)
        assert a == b  # the acceptance contract: same seed, same plan
        assert any(a) and not all(a)  # p=0.4 actually mixes
        assert schedule(8) != a  # and the seed actually matters

    def test_times_and_after_bound_the_schedule(self):
        plan = faults.FaultPlan(
            [_rule(site="s", kind="drop", times=2, after=1)], seed=0
        )
        fired = [plan.on_call("s") is not None for _ in range(6)]
        assert fired == [False, True, True, False, False, False]
        st = plan.stats()["rules"][0]
        assert st["calls"] == 6 and st["fired"] == 2

    def test_first_matching_rule_wins(self):
        plan = faults.FaultPlan(
            [
                _rule(site="s:*", kind="error", status=500),
                _rule(site="s:x", kind="drop"),
            ],
            seed=0,
        )
        act = plan.on_call("s:x")
        assert act.kind == "error" and act.rule == 0

    def test_parse_spec(self):
        rules = faults.parse_spec(
            "site=server:*:/pevents/*,kind=drop,times=2;"
            "site=client:storage:/levents/*,kind=latency,latency_ms=250,p=0.1"
        )
        assert len(rules) == 2
        assert rules[0].site == "server:*:/pevents/*" and rules[0].times == 2
        assert rules[1].latency_ms == 250.0 and rules[1].p == 0.1
        with pytest.raises(ValueError, match="site= and kind="):
            faults.parse_spec("kind=drop")
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse_spec("site=s,kind=nuke")

    def test_env_spec_loads_lazily(self, monkeypatch):
        monkeypatch.setenv(
            "PIO_FAULT_SPEC", "site=s,kind=latency,latency_ms=1"
        )
        monkeypatch.setenv("PIO_FAULT_SEED", "9")
        monkeypatch.setattr(faults, "_active", None)
        monkeypatch.setattr(faults, "_env_loaded", False)
        plan = faults.active()
        assert plan is not None and plan.seed == 9


# -- resilience primitives (no network) --------------------------------------


class TestResiliencePrimitives:
    def test_breaker_open_halfopen_close(self):
        clock = [0.0]
        br = CircuitBreaker(
            "ep", failure_threshold=2, reset_timeout_s=5.0,
            clock=lambda: clock[0],
        )
        assert br.allow()
        br.record_failure()
        assert br.state == "closed" and br.allow()
        br.record_failure()
        assert br.state == "open" and br.open_count == 1
        assert not br.allow()  # fast-fail while open
        assert br.fast_failures == 1
        assert 0 < br.retry_after_s() <= 5.0
        clock[0] = 5.1
        assert br.allow()  # cooldown elapsed: one half-open probe
        assert br.state == "half_open"
        assert not br.allow()  # second caller rejected while probe in flight
        br.record_success()
        assert br.state == "closed" and br.allow()

    def test_halfopen_probe_failure_reopens(self):
        clock = [0.0]
        br = CircuitBreaker(
            "ep", failure_threshold=1, reset_timeout_s=1.0,
            clock=lambda: clock[0],
        )
        br.record_failure()
        clock[0] = 1.5
        assert br.allow()
        br.record_failure()  # probe failed
        assert br.state == "open" and br.open_count == 2

    def test_halfopen_probe_nonretryable_releases_slot(self):
        """A probe that dies with a NON-retryable error (HTTP 400 from a
        legacy replica) must release the half-open probe slot — otherwise
        the breaker wedges in HALF_OPEN rejecting every call forever."""
        clock = [0.0]
        br = CircuitBreaker(
            "ep", failure_threshold=1, reset_timeout_s=1.0,
            clock=lambda: clock[0],
        )
        br.record_failure()  # trip it
        clock[0] = 1.5  # cooldown elapsed: next call is the probe

        def bad_request():
            raise NetworkStorageError("bad", status=400)

        with pytest.raises(NetworkStorageError):
            call_with_resilience(
                bad_request, RetryPolicy(max_attempts=3), breaker=br,
                sleep=lambda s: None,
            )
        assert br.state == "half_open"  # health still unjudged...
        assert br.allow()  # ...but the slot is free: a new probe can run
        br.record_success()
        assert br.state == "closed"

    def test_retry_budget_caps_amplification(self):
        calls = []

        def fail():
            calls.append(1)
            raise NetworkStorageError("boom")  # status None: retryable

        policy = RetryPolicy(
            max_attempts=5, base_backoff_s=0.0,
            budget=RetryBudget(ratio=0.0, cap=1.0),
        )
        with pytest.raises(NetworkStorageError):
            call_with_resilience(fail, policy, sleep=lambda s: None)
        assert len(calls) == 2  # one attempt + the single budgeted retry

    def test_nonretryable_skips_retries_and_breaker(self):
        br = CircuitBreaker("ep", failure_threshold=1)
        calls = []

        def bad_request():
            calls.append(1)
            raise NetworkStorageError("bad", status=400)

        with pytest.raises(NetworkStorageError):
            call_with_resilience(
                bad_request, RetryPolicy(max_attempts=3), breaker=br,
                sleep=lambda s: None,
            )
        assert len(calls) == 1
        assert br.state == "closed"  # a 400 says nothing about endpoint health

    def test_deadline_bounds_retries(self):
        def fail():
            raise NetworkStorageError("boom")

        with pytest.raises(DeadlineExceeded):
            call_with_resilience(
                fail,
                RetryPolicy(max_attempts=10, base_backoff_s=5.0, jitter=0.0),
                deadline=Deadline.after_ms(50),
                sleep=lambda s: None,
            )

    def test_deadline_header_parse(self):
        assert parse_deadline_header(None) is None
        assert parse_deadline_header("garbage") is None
        d = parse_deadline_header("250")
        assert d is not None and 0 < d.remaining_ms() <= 250
        assert parse_deadline_header("-5").expired()

    def test_seeded_policy_replays_backoffs(self):
        a = RetryPolicy(max_attempts=5, seed=3)
        b = RetryPolicy(max_attempts=5, seed=3)
        assert [a.backoff_s(i) for i in (1, 2, 3)] == [
            b.backoff_s(i) for i in (1, 2, 3)
        ]


# -- storage client vs a faulty server/transport -----------------------------


def _mem_storage(name):
    return Storage(env={
        f"PIO_STORAGE_SOURCES_{name}_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": name,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": name,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": name,
    })


def _net_client(port, **overrides):
    env = {
        "PIO_STORAGE_SOURCES_NET_TYPE": "network",
        "PIO_STORAGE_SOURCES_NET_URL": f"http://127.0.0.1:{port}",
        "PIO_STORAGE_SOURCES_NET_SECRET": "s3cret",
        "PIO_STORAGE_SOURCES_NET_RETRIES": "3",
        "PIO_STORAGE_SOURCES_NET_BACKOFF_MS": "5",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "NET",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NET",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "NET",
    }
    env.update({f"PIO_STORAGE_SOURCES_NET_{k}": v for k, v in overrides.items()})
    return Storage(env=env)


@pytest.fixture()
def served():
    name = "C" + uuid.uuid4().hex[:8].upper()
    backing = _mem_storage(name)
    server = StorageServer(backing, secret="s3cret")
    port = server.start("127.0.0.1", 0)
    client = _net_client(port)
    yield {"server": server, "backing": backing, "client": client, "port": port}
    server.stop()
    from predictionio_tpu.data.storage import memory

    memory.reset_store(name)


class TestStorageChaos:
    def test_retry_recovers_dropped_call(self, served):
        faults.install(faults.FaultPlan(
            [_rule(site="client:storage:/meta/apps/*", kind="drop", times=1)],
            seed=1,
        ))
        apps = served["client"].get_meta_data_apps()
        app_id = apps.insert(App(0, "chaos"))  # first call drops, retry lands
        assert apps.get(app_id).name == "chaos"
        assert apps._c.retry_count >= 1
        stats = apps._c.resilience_stats()
        assert stats["retries"] == apps._c.retry_count
        assert "/meta/apps" in stats["breakers"]

    def test_server_5xx_retried_to_success(self, served):
        backing_apps = served["backing"].get_meta_data_apps()
        app_id = backing_apps.insert(App(0, "chaos5xx"))
        served["backing"].get_l_events().init(app_id)
        faults.install(faults.FaultPlan(
            [_rule(site="server:storageserver:/levents/insert",
                   kind="error", status=503, times=2)],
            seed=2,
        ))
        le = served["client"].get_l_events()
        eid = le.insert(Event(event="$set", entity_type="user",
                              entity_id="u1"), app_id)
        assert le.get(eid, app_id) is not None
        assert le._c.retry_count >= 2

    def test_breaker_opens_then_halfopen_probe_closes(self, served):
        client = _net_client(
            served["port"], RETRIES="1",
            BREAKER_THRESHOLD="2", BREAKER_RESET_MS="200",
        )
        apps = client.get_meta_data_apps()
        faults.install(faults.FaultPlan(
            [_rule(site="client:storage:/meta/apps/*", kind="error",
                   status=503)],
            seed=3,
        ))
        for _ in range(2):
            with pytest.raises(NetworkStorageError):
                apps.get_all()
        br = apps._c.breaker_for("/meta/apps")
        assert br.state == "open"
        # open breaker fails FAST: no socket, no timeout, BreakerOpen
        with pytest.raises(BreakerOpen):
            apps.get_all()
        assert br.fast_failures >= 1
        # cooldown → half-open probe; fault plan cleared so the probe
        # succeeds and the breaker closes again
        faults.clear()
        time.sleep(0.25)
        assert apps.get_all() == []
        assert br.state == "closed"

    def _seed_events(self, served, n=40):
        backing_apps = served["backing"].get_meta_data_apps()
        app_id = backing_apps.insert(App(0, "framed"))
        le = served["backing"].get_l_events()
        le.init(app_id)
        le.batch_insert(
            [
                Event(event="rate", entity_type="user", entity_id=f"u{i%7}",
                      target_entity_type="item", target_entity_id=f"i{i%5}",
                      properties={"rating": float(i % 5 + 1)})
                for i in range(n)
            ],
            app_id,
        )
        return app_id

    def test_truncated_frame_stream_retried_client_side(self, served):
        app_id = self._seed_events(served)
        faults.install(faults.FaultPlan(
            [_rule(site="client:storage:frames:/pevents/find",
                   kind="truncate", times=1)],
            seed=4,
        ))
        pe = served["client"].get_p_events()
        batch = pe.find(app_id)
        assert len(batch) == 40  # full result despite the torn first pull
        assert pe._c.retry_count >= 1

    def test_truncated_frame_stream_retried_server_side(self, served):
        """The server tears the chunked stream MID-frame; the client must
        see a truncation error (never a silently-short result) and the
        policy layer must recover it."""
        app_id = self._seed_events(served)
        faults.install(faults.FaultPlan(
            [_rule(site="server:storageserver:/pevents/find",
                   kind="truncate", times=1)],
            seed=5,
        ))
        pe = served["client"].get_p_events()
        batch = pe.find(app_id)
        assert len(batch) == 40
        assert pe._c.retry_count >= 1


# -- http fault shim: truncate scoping --------------------------------------


class TestHttpFaultShim:
    def _service(self, pieces):
        from predictionio_tpu.common.http import (
            HttpService,
            Response,
            json_response,
        )

        svc = HttpService("shim")

        @svc.route("GET", r"/plain")
        def plain(req):
            return json_response(200, {"ok": True})

        @svc.route("GET", r"/stream")
        def stream(req):
            return Response(status=200, body=iter(pieces))

        port = svc.start("127.0.0.1", 0)
        return svc, port

    def test_truncate_flag_scoped_to_faulted_request(self):
        """A truncate fault on a non-streamed response must NOT survive the
        keep-alive connection and tear a later stream the seeded plan never
        scheduled."""
        import http.client

        svc, port = self._service([b"abcd", b"efgh"])
        try:
            faults.install(faults.FaultPlan(
                [_rule(site="server:shim:/plain", kind="truncate", times=1)],
                seed=6,
            ))
            conn = http.client.HTTPConnection("127.0.0.1", port)
            try:
                conn.request("GET", "/plain")
                r = conn.getresponse()
                assert r.status == 200 and r.read()  # non-streamed: unaffected
                # same keep-alive socket, next request: no fault scheduled
                conn.request("GET", "/stream")
                r = conn.getresponse()
                assert r.read() == b"abcdefgh"  # intact, cleanly terminated
            finally:
                conn.close()
        finally:
            svc.stop()

    def test_truncate_tears_first_nonempty_piece(self):
        """An empty leading piece must not turn the injected tear into a
        cleanly-terminated empty stream: the cut lands on real bytes and the
        client sees a torn chunked body."""
        import http.client

        svc, port = self._service([b"", b"payload-bytes"])
        try:
            faults.install(faults.FaultPlan(
                [_rule(site="server:shim:/stream", kind="truncate", times=1)],
                seed=7,
            ))
            conn = http.client.HTTPConnection("127.0.0.1", port)
            try:
                conn.request("GET", "/stream")
                r = conn.getresponse()
                with pytest.raises(
                    (http.client.IncompleteRead, ConnectionError)
                ):
                    r.read()
            finally:
                conn.close()
            assert faults.active().stats()["rules"][0]["fired"] == 1
        finally:
            svc.stop()


# -- query server: deadlines, shedding, degraded fallback --------------------


@pytest.fixture()
def trained(storage):
    store_mod.set_storage(storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "chaosapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(5)
    le.batch_insert(
        [
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties={"rating": float(rng.integers(1, 6))})
            for u in range(8)
            for i in rng.choice(8, size=4, replace=False)
        ],
        app_id,
    )
    engine = RecommendationEngine.apply()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "chaosapp"}},
        "algorithms": [
            {"name": "als", "params": {"rank": 2, "numIterations": 2}}
        ],
    })
    ctx = MeshContext.create()
    run_train(engine, ep, "chaos", storage=storage, ctx=ctx)
    yield {"storage": storage, "engine": engine, "ctx": ctx}
    store_mod.set_storage(None)


def _call(method, url, body=None, headers=None):
    data = json.dumps(body).encode() if body is not None else None
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=data, method=method, headers=hdrs)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read().decode()), r.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), e.headers


class TestQueryServerChaos:
    def _server(self, trained, **kw):
        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"], **kw,
        )
        port = qs.start("127.0.0.1", 0)
        return qs, f"http://127.0.0.1:{port}"

    def test_healthz_readyz(self, trained):
        qs, base = self._server(trained)
        try:
            status, body, _ = _call("GET", base + "/healthz")
            assert status == 200 and body["status"] == "ok"
            status, body, _ = _call("GET", base + "/readyz")
            assert status == 200 and body["status"] == "ready"
            assert body["deployed"] and not body["reloadDegraded"]
        finally:
            qs.stop()

    def test_overload_sheds_with_retry_after(self, trained):
        qs, base = self._server(trained, max_inflight=0,
                                shed_retry_after_s=2.0)
        try:
            status, body, headers = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 503 and "shed" in body["message"]
            assert headers.get("Retry-After") == "2"
            status, body, _ = _call("GET", base + "/readyz")
            assert status == 503 and body["status"] == "overloaded"
            status, info, _ = _call("GET", base + "/")
            assert info["resilience"]["counters"]["shed"] == 1
        finally:
            qs.stop()

    def test_expired_deadline_shed_before_device(self, trained):
        qs, base = self._server(trained)
        try:
            status, _, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200  # warm: a live path works
            algo = qs._deployed.algorithms[0]
            orig = algo.predict
            calls = []
            algo.predict = lambda m, q: (calls.append(1), orig(m, q))[1]
            status, body, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2},
                headers={"X-Request-Deadline": "0"},
            )
            assert status == 504
            assert calls == []  # never reached the scorer, let alone device
            status, info, _ = _call("GET", base + "/")
            assert info["resilience"]["counters"]["deadline_exceeded"] == 1
        finally:
            qs.stop()

    def test_default_deadline_applies_without_header(self, trained):
        qs, base = self._server(trained, default_deadline_ms=0.0)
        try:
            status, _, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 504
        finally:
            qs.stop()

    def test_scorer_failure_serves_degraded_not_500(self, trained):
        qs, base = self._server(trained)
        try:
            status, good, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200 and "degraded" not in good
            algo = qs._deployed.algorithms[0]
            algo.predict = lambda m, q: (_ for _ in ()).throw(
                RuntimeError("scorer down")
            )
            status, body, _ = _call(
                "POST", base + "/queries.json", {"user": "u2", "num": 2}
            )
            assert status == 200 and body["degraded"] is True
            assert body["itemScores"] == good["itemScores"]  # last good answer
            status, info, _ = _call("GET", base + "/")
            assert info["resilience"]["counters"]["degraded"] == 1
            # scorer recovers → fresh answers, flag gone
            del algo.predict
            status, body, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200 and "degraded" not in body
        finally:
            qs.stop()

    def test_malformed_query_still_400_despite_fallback(self, trained):
        """TypeError from bad query values is a CLIENT bug: it must map to
        HTTP 400 even when a degraded fallback is available, never a 200
        with a stale answer (which would also pollute the degraded gate)."""
        qs, base = self._server(trained)
        try:
            status, _, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200  # _last_good is now populated
            algo = qs._deployed.algorithms[0]
            algo.predict = lambda m, q: (_ for _ in ()).throw(
                TypeError("num must be an int")
            )
            status, body, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 400 and "num must be an int" in body["message"]
            status, info, _ = _call("GET", base + "/")
            counters = info["resilience"]["counters"]
            assert counters["degraded"] == 0
            assert counters["query_errors"] == 1
        finally:
            qs.stop()

    def test_clean_run_leaves_every_counter_quiet(self, trained):
        """No fault planted, no overload: a load test through the batched
        path answers everything and the resilience layer records nothing —
        any shed, deadline, degraded answer or query error here is a
        regression, not noise."""
        from predictionio_tpu.tools.loadtest import run_loadtest

        qs, base = self._server(trained, batching=True)
        try:
            res = run_loadtest(
                base, {"num": 3}, requests=48, concurrency=4,
                samples={"user": [f"u{u}" for u in range(8)]},
            )
            assert res["ok"] == 48 and res["errors"] == 0
            assert res["shed"] == 0 and res["deadlineExceeded"] == 0
            _, info, _ = _call("GET", base + "/")
            counters = info["resilience"]["counters"]
            for name in ("shed", "deadline_exceeded", "breaker_open",
                         "degraded", "query_errors"):
                assert counters.get(name, 0) == 0, (name, counters)
        finally:
            qs.stop()

    def test_loadtest_carries_deadline_and_breaks_out_sheds(self, trained):
        from predictionio_tpu.tools.loadtest import run_loadtest

        qs, base = self._server(trained)
        try:
            res = run_loadtest(
                base, {"user": "u1", "num": 2}, requests=5, concurrency=2,
                deadline_ms=0.0,
            )
            assert res["deadlineExceeded"] == 5
            assert res["errors"] == 0 and res["ok"] == 0
        finally:
            qs.stop()


# -- micro-batcher deadline semantics ----------------------------------------


class TestBatcherDeadlines:
    def test_pre_expired_submit_never_executes(self):
        executed = []

        def run(batch):
            executed.extend(batch)
            return list(batch)

        mb = MicroBatcher(run, max_batch=4)
        try:
            with pytest.raises(DeadlineExceeded):
                mb.submit("q", deadline=Deadline.after_ms(-1))
            assert executed == []
            assert mb.stats()["expired_dropped"] == 1
        finally:
            mb.stop()

    def test_expired_in_queue_dropped_at_dispatch(self):
        """A waiter that timed out must never have its query run on device:
        the worker drops the expired pending at dispatch."""
        executed = []
        first_started = threading.Event()

        def run(batch):
            executed.extend(batch)
            if batch == ["slow"]:
                first_started.set()
                time.sleep(0.3)  # hold _busy so the next submit queues
            return list(batch)

        mb = MicroBatcher(run, max_batch=4)
        try:
            t = threading.Thread(
                target=lambda: mb.submit("slow"), daemon=True
            )
            t.start()
            assert first_started.wait(2.0)
            with pytest.raises(DeadlineExceeded):
                mb.submit("doomed", timeout=0.05)
            t.join(2.0)
            deadline = time.monotonic() + 2.0
            while mb.stats()["expired_dropped"] < 1:
                assert time.monotonic() < deadline, "pending never dropped"
                time.sleep(0.01)
            assert "doomed" not in executed
        finally:
            mb.stop()

    def test_live_requests_unaffected_by_deadline_plumbing(self):
        mb = MicroBatcher(lambda b: [x * 2 for x in b], max_batch=4)
        try:
            assert mb.submit(21, deadline=Deadline.after_ms(5000)) == 42
            assert mb.stats()["expired_dropped"] == 0
        finally:
            mb.stop()


# -- telemetry under chaos (obs/): the metrics you'd watch an outage with ----


class TestTelemetryUnderChaos:
    def test_breaker_metrics_walk_closed_open_halfopen(self, served):
        """The pio_storage_client_* series must track the breaker's real
        state machine under a fault shim: 0 → 1 → 2 → 0, with the retry
        counter and opens_total moving when they should."""
        from predictionio_tpu.obs import bridges as obs_bridges
        from predictionio_tpu.obs import metrics as obs_metrics

        client = _net_client(
            served["port"], RETRIES="2",
            BREAKER_THRESHOLD="2", BREAKER_RESET_MS="200",
        )
        apps = client.get_meta_data_apps()
        reg = obs_metrics.MetricsRegistry()
        obs_bridges.bridge_resilience(reg, client.resilience_stats)

        def series():
            return obs_metrics.parse_prometheus(reg.render_prometheus())

        def gauge(name):
            return series().get(
                (f"pio_storage_client_{name}",
                 (("endpoint", "/meta/apps"),))
            )

        # CLOSED: a healthy call creates the breaker, state reads 0
        assert apps.get_all() == []
        assert gauge("breaker_state") == 0
        assert series()[("pio_storage_client_retries_total", ())] == 0

        # persistent 503s: RETRIES=2 means one failing call burns two
        # attempts — threshold 2 trips the breaker OPEN on the spot
        faults.install(faults.FaultPlan(
            [_rule(site="client:storage:/meta/apps/*", kind="error",
                   status=503)],
            seed=11,
        ))
        with pytest.raises(NetworkStorageError):
            apps.get_all()
        assert gauge("breaker_state") == 1
        assert gauge("breaker_opens_total") == 1
        assert series()[("pio_storage_client_retries_total", ())] >= 1

        # cooldown elapses; a slow probe holds the breaker in HALF_OPEN
        # long enough for a scrape to see state 2 mid-flight
        faults.clear()
        faults.install(faults.FaultPlan(
            [_rule(site="client:storage:/meta/apps/*", kind="latency",
                   latency_ms=400, times=1)],
            seed=12,
        ))
        time.sleep(0.25)
        probe = threading.Thread(target=apps.get_all, daemon=True)
        probe.start()
        saw_half_open = False
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            if gauge("breaker_state") == 2:
                saw_half_open = True
                break
            time.sleep(0.01)
        probe.join(5.0)
        assert saw_half_open, "scrape never observed HALF_OPEN"
        # probe succeeded → CLOSED again, and the trip count is history
        assert gauge("breaker_state") == 0
        assert gauge("breaker_opens_total") == 1

    def test_metrics_keeps_serving_while_degraded(self, trained):
        """/metrics must answer — and show the degradation — while the
        scorer is down and queries are being served from the fallback."""
        from predictionio_tpu.obs import metrics as obs_metrics

        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"],
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            status, _, _ = _call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2}
            )
            assert status == 200  # warm: _last_good is populated
            algo = qs._deployed.algorithms[0]
            algo.predict = lambda m, q: (_ for _ in ()).throw(
                RuntimeError("scorer down")
            )
            for _ in range(3):
                status, body, _ = _call(
                    "POST", base + "/queries.json", {"user": "u2", "num": 2}
                )
                assert status == 200 and body["degraded"] is True
            with urllib.request.urlopen(base + "/metrics") as r:
                assert r.status == 200
                text = r.read().decode()
            series = obs_metrics.parse_prometheus(text)
            assert series[
                ("pio_query_errors_total", (("kind", "degraded"),))
            ] == 3
            # the exposition itself stays whole mid-outage
            assert len(series) >= 25
        finally:
            qs.stop()
