"""Query server + batch predict over live HTTP with the recommendation engine.

Parity model: the quickstart tier-3 scenario's deploy/query/undeploy phase +
CreateServer route behavior (SURVEY.md §3.2).
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core.workflow import run_train
from predictionio_tpu.data import Event
from predictionio_tpu.data import store as store_mod
from predictionio_tpu.data.storage import AccessKey, App
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.batch_predict import run_batch_predict
from predictionio_tpu.serving.query_server import EngineServerPlugin, QueryServer
from predictionio_tpu.templates.recommendation import RecommendationEngine


def call(method, url, body=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


@pytest.fixture()
def trained(storage):
    store_mod.set_storage(storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "qsapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(3)
    events = []
    for u in range(20):
        for i in rng.choice(16, size=6, replace=False):
            events.append(
                Event(
                    event="rate",
                    entity_type="user",
                    entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties={"rating": float(rng.integers(1, 6))},
                )
            )
    le.batch_insert(events, app_id)
    engine = RecommendationEngine.apply()
    ep = engine.params_from_variant(
        {
            "datasource": {"params": {"appName": "qsapp"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, "numIterations": 3}}
            ],
        }
    )
    ctx = MeshContext.create()
    run_train(engine, ep, "f", storage=storage, ctx=ctx)
    yield {"storage": storage, "engine": engine, "ctx": ctx, "ep": ep}
    store_mod.set_storage(None)


class UpperCasePlugin(EngineServerPlugin):
    name = "upper"
    plugin_type = EngineServerPlugin.OUTPUT_BLOCKER

    def process(self, query, prediction, context):
        prediction["itemScores"] = prediction["itemScores"][:1]
        return prediction


class TestQueryServer:
    def test_query_info_reload_stop(self, trained):
        qs = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"]
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            status, res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 3}
            )
            assert status == 200 and len(res["itemScores"]) == 3

            # unknown JSON fields are ignored (lenient query binding)
            status, res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 2, "zzz": 1}
            )
            assert status == 200 and len(res["itemScores"]) == 2

            status, info = call("GET", base + "/")
            assert info["requestCount"] == 2 and info["engineInstanceId"]
            first_iid = info["engineInstanceId"]

            # retrain → /reload picks up the NEW instance
            run_train(
                trained["engine"], trained["ep"], "f",
                storage=trained["storage"], ctx=trained["ctx"],
            )
            status, body = call("GET", base + "/reload")
            assert status == 200 and body["engineInstanceId"] != first_iid

            status, res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 1}
            )
            assert status == 200  # serving continued across reload
        finally:
            status, body = call("POST", base + "/stop")
            assert "Shutting down" in body["message"]
            deadline = time.time() + 5  # /stop delays ~0.3s to flush response
            while time.time() < deadline:
                try:
                    call("GET", base + "/")
                    time.sleep(0.1)
                except Exception:
                    break
            else:
                pytest.fail("server still alive after /stop")

    def test_output_blocker_plugin_and_plugins_route(self, trained):
        qs = QueryServer(
            trained["engine"],
            storage=trained["storage"],
            ctx=trained["ctx"],
            plugins=[UpperCasePlugin()],
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            status, res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 5}
            )
            assert len(res["itemScores"]) == 1  # blocker rewrote the output
            status, plugins = call("GET", base + "/plugins.json")
            assert "upper" in plugins["plugins"]["outputblockers"]
        finally:
            qs.stop()

    def test_feedback_loop_posts_to_event_server(self, trained):
        from predictionio_tpu.data.api.event_server import EventServer

        storage = trained["storage"]
        key = storage.get_meta_data_access_keys().insert(
            AccessKey("", storage.get_meta_data_apps().get_by_name("qsapp").id, [])
        )
        es = EventServer(storage=storage)
        es_port = es.start("127.0.0.1", 0)
        qs = QueryServer(
            trained["engine"],
            storage=storage,
            ctx=trained["ctx"],
            feedback=True,
            event_server_url=f"http://127.0.0.1:{es_port}",
            access_key=key,
        )
        port = qs.start("127.0.0.1", 0)
        try:
            status, res = call(
                "POST",
                f"http://127.0.0.1:{port}/queries.json",
                {"user": "u2", "num": 2},
            )
            assert "prId" in res
            deadline = time.time() + 5
            feedback_events = []
            while time.time() < deadline and not feedback_events:
                feedback_events = list(
                    storage.get_l_events().find(
                        storage.get_meta_data_apps().get_by_name("qsapp").id,
                        event_names=["predict"],
                    )
                )
                time.sleep(0.05)
            assert feedback_events, "feedback event never arrived"
            props = feedback_events[0].properties
            assert props["prediction"]["prId"] == res["prId"]
        finally:
            qs.stop()
            es.stop()

    def test_process_spanning_pod_mesh_refuses_routed_traffic(self, trained):
        """A replica whose pod mesh spans jax.distributed processes is
        lockstep-only: /readyz reports not-ready with the group advert
        withheld, and /queries.json refuses rather than dispatching a
        collective its SPMD peers would never join."""
        qs = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"]
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            status, _res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 1}
            )
            assert status == 200  # sanity: serves before the override
            qs._fastpath_stats = lambda: {
                "pod": {
                    "host_groups": 2,
                    "spans_processes": True,
                    "fingerprint": "fp-pod",
                    "process_index": 0,
                    "process_count": 2,
                }
            }
            qs._pod_lockstep_memo = None  # drop the memoized verdict
            status, body = call("GET", base + "/readyz")
            assert status == 503
            assert "lockstep" in body["status"]
            assert body["pod"]["group"] is None
            assert body["pod"]["spansProcesses"] is True
            status, body = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 1}
            )
            assert status == 503
            assert "lockstep" in body["message"]
        finally:
            qs.stop()


class TestMicroBatching:
    def test_concurrent_queries_batched_and_identical(self, trained):
        import threading

        from predictionio_tpu.serving.query_server import QueryServer

        plain = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"]
        )
        batched = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"],
            batching=True,
        )
        # count device-batch invocations
        calls = []
        orig = batched._run_query_batch

        def counting(queries):
            calls.append(len(queries))
            return orig(queries)

        batched._batcher._run_batch = counting
        p_plain = plain.start("127.0.0.1", 0)
        p_batch = batched.start("127.0.0.1", 0)
        try:
            # 64 concurrent connects overflowed the stdlib default accept
            # backlog (5) before common/http.py raised request_queue_size
            users = [f"u{i % 10}" for i in range(64)]
            results = {}

            def fire(base, tag):
                def go(u, i):
                    _, res = call(
                        "POST", f"http://127.0.0.1:{base}/queries.json",
                        {"user": u, "num": 3},
                    )
                    results[(tag, i)] = res

                threads = [
                    threading.Thread(target=go, args=(u, i))
                    for i, u in enumerate(users)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()

            fire(p_batch, "batch")
            fire(p_plain, "plain")
            for i in range(len(users)):
                b, p = results[("batch", i)], results[("plain", i)]
                assert [s["item"] for s in b["itemScores"]] == [
                    s["item"] for s in p["itemScores"]
                ], i
                for sb, sp in zip(b["itemScores"], p["itemScores"]):
                    # batched GEMM vs per-query GEMV: last-ulp differences
                    assert abs(sb["score"] - sp["score"]) < 1e-4
            # concurrency actually coalesced: fewer batch calls than requests
            assert sum(calls) == len(users)
            assert len(calls) < len(users)
        finally:
            plain.stop()
            batched.stop()

    def test_plugins_see_supplemented_query_in_both_modes(self, trained):
        """Plugins/feedback receive the serving-supplemented query whether or
        not micro-batching is on (parity: CreateServer's single
        supplement-then-serve pipeline)."""
        import dataclasses as dc

        from predictionio_tpu.serving.query_server import (
            EngineServerPlugin,
            QueryServer,
        )

        seen: dict[str, list] = {"plain": [], "batch": []}

        def recorder(tag):
            class Recorder(EngineServerPlugin):
                name = f"recorder-{tag}"
                plugin_type = EngineServerPlugin.OUTPUT_SNIFFER

                def process(self, query, prediction, context):
                    seen[tag].append(query)
                    return prediction

            return Recorder()

        servers = []
        try:
            for tag, batching in (("plain", False), ("batch", True)):
                qs = QueryServer(
                    trained["engine"], storage=trained["storage"],
                    ctx=trained["ctx"], plugins=[recorder(tag)],
                    batching=batching,
                )
                # make supplement observable: tag the query it returns
                serving = qs._deployed.serving
                if not getattr(serving, "_test_patched", False):
                    orig = serving.supplement
                    serving.supplement = lambda q, _o=orig: dc.replace(
                        _o(q), num=q.num + 1
                    )
                    serving._test_patched = True
                port = qs.start("127.0.0.1", 0)
                servers.append(qs)
                status, _ = call(
                    "POST", f"http://127.0.0.1:{port}/queries.json",
                    {"user": "u1", "num": 3},
                )
                assert status == 200
            assert len(seen["plain"]) == 1 and len(seen["batch"]) == 1
            # both modes hand plugins the SUPPLEMENTED query, not the raw one
            assert seen["plain"][0].num > 3
            assert seen["batch"][0].num == seen["plain"][0].num
            assert seen["batch"][0].user == seen["plain"][0].user
        finally:
            for qs in servers:
                qs.stop()

    def test_batch_error_propagates_per_request(self, trained):
        from predictionio_tpu.serving.query_server import QueryServer

        qs = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"],
            batching=True,
        )
        qs._batcher._run_batch = lambda queries: (_ for _ in ()).throw(
            RuntimeError("boom")
        )
        port = qs.start("127.0.0.1", 0)
        try:
            status, body = call(
                "POST", f"http://127.0.0.1:{port}/queries.json",
                {"user": "u1", "num": 2},
            )
            assert status == 500 and "boom" in body["message"]
        finally:
            qs.stop()


class TestFullyLoadedServer:
    def test_batching_feedback_plugins_together(self, trained):
        """All server features enabled at once behave correctly."""
        from predictionio_tpu.data.api.event_server import EventServer
        from predictionio_tpu.serving.query_server import QueryServer

        storage = trained["storage"]
        key = storage.get_meta_data_access_keys().insert(
            AccessKey("", storage.get_meta_data_apps().get_by_name("qsapp").id, [])
        )
        es = EventServer(storage=storage)
        es_port = es.start("127.0.0.1", 0)
        qs = QueryServer(
            trained["engine"],
            storage=storage,
            ctx=trained["ctx"],
            batching=True,
            feedback=True,
            event_server_url=f"http://127.0.0.1:{es_port}",
            access_key=key,
            plugins=[UpperCasePlugin()],
        )
        port = qs.start("127.0.0.1", 0)
        try:
            status, res = call(
                "POST", f"http://127.0.0.1:{port}/queries.json",
                {"user": "u1", "num": 5},
            )
            assert status == 200
            assert len(res["itemScores"]) == 1  # blocker truncated
            assert "prId" in res  # feedback tagged
            deadline = time.time() + 5
            app_id = storage.get_meta_data_apps().get_by_name("qsapp").id
            while time.time() < deadline:
                fb = list(
                    storage.get_l_events().find(app_id, event_names=["predict"])
                )
                if fb:
                    break
                time.sleep(0.05)
            assert fb, "feedback event missing with batching enabled"
        finally:
            qs.stop()
            es.stop()


class TestLoadtest:
    def test_loadtest_reports(self, trained):
        from predictionio_tpu.serving.query_server import QueryServer
        from predictionio_tpu.tools.loadtest import run_loadtest

        qs = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"]
        )
        port = qs.start("127.0.0.1", 0)
        try:
            result = run_loadtest(
                f"http://127.0.0.1:{port}",
                {"user": "u1", "num": 3},
                requests=40,
                concurrency=4,
            )
            assert result["ok"] == 40 and result["errors"] == 0
            assert result["qps"] > 0 and result["p50Ms"] > 0
            assert result["p50Ms"] <= result["p99Ms"]
        finally:
            qs.stop()

    def test_loadtest_samples_rotate_users(self):
        """The `samples` rotation must send EVERY listed value, evenly
        (mixed-key tail measurement, VERDICT r4) — asserted against a
        stub server that records each request's payload."""
        import threading
        from collections import Counter
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from predictionio_tpu.tools.loadtest import run_loadtest

        seen = Counter()
        lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                q = json.loads(body)
                with lock:
                    seen[q["user"]] += 1
                out = b"{}"
                self.send_response(200)
                self.send_header("Content-Length", str(len(out)))
                self.end_headers()
                self.wfile.write(out)

            def log_message(self, *a):
                pass

        srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            users = [f"u{i}" for i in range(8)]
            result = run_loadtest(
                f"http://127.0.0.1:{srv.server_port}",
                {"num": 3},
                requests=24,
                concurrency=3,
                samples={"user": users},
            )
            assert result["ok"] == 24 and result["errors"] == 0
            # round-robin: every user exactly requests/len(users) times
            assert seen == Counter({u: 3 for u in users})
        finally:
            srv.shutdown()

    def test_zipf_mandelbrot_weights_cover_range_and_skew(self):
        import numpy as np

        from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights

        p = zipf_mandelbrot_weights(1000, s=1.1, q=50.0)
        assert p.shape == (1000,) and abs(p.sum() - 1.0) < 1e-12
        assert (np.diff(p) < 0).all()  # rank 0 is the hottest key
        rng = np.random.default_rng(0)
        z = rng.choice(1000, size=100_000, p=p)
        u = rng.integers(0, 1000, 100_000)
        assert z.min() >= 0 and z.max() < 1000
        # zipf concentrates mass on low ids far beyond uniform
        assert (z < 50).mean() > 2 * (u < 50).mean()


class TestBatchPredict:
    def test_batch_predict_file(self, trained, tmp_path):
        inp = tmp_path / "queries.json"
        out = tmp_path / "out.json"
        inp.write_text(
            "\n".join(
                [
                    json.dumps({"user": "u1", "num": 2}),
                    "",
                    json.dumps({"user": "u2", "num": 1}),
                    "not-json",
                ]
            )
        )
        n, written = run_batch_predict(
            trained["engine"],
            str(inp),
            str(out),
            storage=trained["storage"],
            ctx=trained["ctx"],
        )
        assert n == 2 and written == str(out)
        lines = [json.loads(l) for l in out.read_text().splitlines()]
        assert len(lines) == 3  # 2 ok + 1 error line
        assert len(lines[0]["prediction"]["itemScores"]) == 2
        assert "error" in lines[2]
