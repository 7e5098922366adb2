"""Tests for the `pio analyze` static-analysis subsystem.

Each analyzer gets a minimal fixture tree that triggers its rules
(positives) and a repo-idiom twin that must stay clean (negatives), so
a loosened heuristic and an over-eager one both fail loudly.  The
framework pieces — suppressions, baseline, JSON schema, the knob
registry — are tested round-trip, and the real checkout must analyze
clean (zero errors) because `pio analyze` gates tier-1.
"""

import json
import os
import textwrap

import pytest

from predictionio_tpu.analysis.core import (
    BASELINE_NAME, RepoIndex, load_baseline, run, write_baseline,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_repo(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def by_rule(report, rule_id):
    return [f for f in report.findings if f.rule == rule_id]


def symbols(report, rule_id):
    return {f.symbol for f in by_rule(report, rule_id)}


# -- framework ----------------------------------------------------------------


def test_finding_key_is_line_independent(tmp_path):
    root = make_repo(tmp_path, {"a.py": "import os\n"})
    k1 = run(root, analyzers=["hygiene"]).findings[0].key
    # push the import down two lines: the key must not move
    (tmp_path / "a.py").write_text('"""doc."""\n\nimport os\n')
    k2 = run(root, analyzers=["hygiene"]).findings[0].key
    assert k1 == k2
    assert "a.py" in k1 and "os" in k1


def test_inline_suppression_same_line_and_standalone(tmp_path):
    root = make_repo(tmp_path, {
        "a.py": "import os  # pio: ignore[hygiene-unused-import]\n",
        "b.py": "# pio: ignore[hygiene-unused-import]\nimport sys\n",
        "c.py": "import json  # pio: ignore\n",
        "d.py": "import re\n",
    })
    rep = run(root, analyzers=["hygiene"])
    assert symbols(rep, "hygiene-unused-import") == {"re"}
    assert rep.suppressed == 3


def test_suppression_for_other_rule_does_not_waive(tmp_path):
    root = make_repo(tmp_path, {
        "a.py": "import os  # pio: ignore[hotpath-host-sync]\n",
    })
    rep = run(root, analyzers=["hygiene"])
    assert symbols(rep, "hygiene-unused-import") == {"os"}


def test_baseline_round_trip(tmp_path):
    root = make_repo(tmp_path, {"a.py": "import os\nimport sys\n"})
    rep = run(root, analyzers=["hygiene"])
    assert len(rep.findings) == 2 and rep.baselined == 0
    baseline = os.path.join(root, BASELINE_NAME)
    write_baseline(baseline, rep.findings)
    assert len(load_baseline(baseline)) == 2
    again = run(root, analyzers=["hygiene"])
    assert again.findings == [] and again.baselined == 2
    # a NEW finding still reports: the baseline is debt, not a blindfold
    (tmp_path / "b.py").write_text("import json\n")
    third = run(root, analyzers=["hygiene"])
    assert symbols(third, "hygiene-unused-import") == {"json"}


def test_baseline_rejects_unknown_format(tmp_path):
    p = tmp_path / "base.json"
    p.write_text('{"version": 9, "findings": []}')
    with pytest.raises(ValueError):
        load_baseline(str(p))


def test_unknown_analyzer_raises(tmp_path):
    root = make_repo(tmp_path, {"a.py": "x = 1\n"})
    with pytest.raises(ValueError):
        run(root, analyzers=["nope"])


def test_changed_only_scopes_the_report(tmp_path):
    root = make_repo(tmp_path, {
        "a.py": "import os\n",
        "b.py": "import sys\n",
    })
    rep = run(root, analyzers=["hygiene"], changed_only={"a.py"})
    assert symbols(rep, "hygiene-unused-import") == {"os"}


def test_report_json_schema(tmp_path):
    root = make_repo(tmp_path, {"a.py": "import os\n"})
    d = run(root, analyzers=["hygiene"]).to_dict()
    assert d["version"] == 1
    assert set(d["counts"]) == {"error", "warning", "info"}
    for key in ("root", "analyzers", "suppressed", "baselined", "findings"):
        assert key in d
    f = d["findings"][0]
    assert set(f) == {
        "rule", "severity", "path", "line", "message", "symbol", "key",
    }
    json.dumps(d)  # must be serializable as-is


# -- hotpath ------------------------------------------------------------------


HOTPATH_FIXTURE = {
    "models/jitted.py": """\
        import jax

        @jax.jit
        def bad_branch(x):
            if x:
                return x
            return -x

        @jax.jit
        def bad_sync(x):
            return float(x)

        @jax.jit
        def bad_loop(xs):
            total = 0
            for v in xs:
                total = total + v
            return total

        from functools import partial

        @partial(jax.jit, static_argnames=("flag",))
        def ok_static(x, flag):
            if flag:
                return x * 2
            return x

        @jax.jit
        def ok_shape(x):
            if x.ndim == 2:
                return x.sum()
            return x
    """,
    "serving/warm.py": """\
        import jax

        def handle_query(model, x):
            y = model(x)
            y.block_until_ready()
            return y

        def warmup(model):
            out = model(0)
            out.block_until_ready()
            return out

        def recommend(model, q):
            f = jax.jit(model)
            return f(q)

        def _compile_scorer(model):
            return jax.jit(model)
    """,
    # IVF retrieval entry points (ops/ivf.py idiom): probe_*/retrieve_*
    # run per cache-miss query, so compiling there stalls a live request
    # — while the publish-time k-means trainer compiles lazily by design.
    "serving/retrieval.py": """\
        import jax

        def probe_clusters(model, q):
            f = jax.jit(model)
            return f(q)

        def retrieve_candidates(model, q):
            f = jax.jit(model)
            return f(q)

        def train_kmeans(model, v):
            return jax.jit(model)(v)
    """,
    # Pallas kernels: a bare-name kernel and a partial-specialised one
    # (ops/score_kernel.py idiom) must both register as traced — the
    # partial's bound keywords are static and branch-safe, while a host
    # sync inside either kernel body must still fire.
    "ops/kern.py": """\
        from functools import partial

        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kern(x_ref, o_ref, *, block, flag):
            if flag:
                o_ref[...] = x_ref[...] * 2.0
            else:
                o_ref[...] = x_ref[...]

        def _bad_partial_kern(x_ref, o_ref, *, block):
            v = x_ref[...]
            o_ref[...] = float(v)

        def launch(x, shape):
            pl.pallas_call(partial(_kern, block=8, flag=True),
                           out_shape=shape)(x)
            pl.pallas_call(partial(_bad_partial_kern, block=8),
                           out_shape=shape)(x)
    """,
    # Variable-assigned partial kernels (ops/train_kernel.py idiom:
    # `kern = partial(_kern, ...)` specialised above the launch) must
    # register as traced exactly like the inline form — bound keywords
    # static, host syncs inside the body still firing.
    "ops/train_kern.py": """\
        from functools import partial

        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _assigned_kern(x_ref, o_ref, *, block, flag):
            if flag:
                o_ref[...] = x_ref[...] * 2.0
            else:
                o_ref[...] = x_ref[...]

        def _bad_assigned_kern(x_ref, o_ref, *, block):
            v = x_ref[...]
            o_ref[...] = float(v)

        def launch(x, shape):
            kern = partial(_assigned_kern, block=8, flag=True)
            pl.pallas_call(kern, out_shape=shape)(x)
            bad = partial(_bad_assigned_kern, block=8)
            pl.pallas_call(bad, out_shape=shape)(x)
    """,
}


def test_hotpath_positives_and_negatives(tmp_path):
    root = make_repo(tmp_path, HOTPATH_FIXTURE)
    rep = run(root, analyzers=["hotpath"])
    assert symbols(rep, "hotpath-traced-branch") == {"bad_branch.x"}
    assert symbols(rep, "hotpath-host-sync") == {
        "bad_sync.float", "_bad_partial_kern.float",
        "_bad_assigned_kern.float",
    }
    assert symbols(rep, "hotpath-traced-loop") == {"bad_loop.xs"}
    assert symbols(rep, "hotpath-block-sync") == {"handle_query"}
    assert symbols(rep, "hotpath-jit-in-request") == {
        "recommend", "probe_clusters", "retrieve_candidates",
    }
    # the publish-time trainer is NOT a request entry point
    assert not any(
        "train_kmeans" in s for s in symbols(rep, "hotpath-jit-in-request")
    )
    # static args, shape checks, warmup fences, compile helpers, and
    # partial-bound kernel keywords (branching on `flag`): clean
    all_syms = {f.symbol for f in rep.findings}
    assert not any("ok_static" in s or "ok_shape" in s or
                   "warmup" in s or "_compile" in s for s in all_syms)
    assert not any(s.startswith("_kern.") for s in all_syms)
    assert not any(s.startswith("_assigned_kern.") for s in all_syms)


# -- races --------------------------------------------------------------------


RACES_FIXTURE = {
    "serving/state.py": """\
        import threading

        class Unguarded:
            def __init__(self):
                self._n = 0

            def bump(self):
                self._n += 1

            def read(self):
                return self._n

        class Guarded:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def bump(self):
                with self._lock:
                    self._n += 1

            def read(self):
                with self._lock:
                    return self._n
    """,
    "common/plan.py": """\
        import threading

        _lock = threading.Lock()
        _plan = None
        _other = None

        def set_plan(p):
            global _plan
            with _lock:
                _plan = p

        def set_other(p):
            global _other
            _other = p
    """,
}


def test_races_positives_and_negatives(tmp_path):
    root = make_repo(tmp_path, RACES_FIXTURE)
    rep = run(root, analyzers=["races"])
    rmw = symbols(rep, "race-unguarded-rmw")
    assert any("Unguarded" in s for s in rmw)
    assert not any("Guarded." in s for s in rmw)
    # module globals: unlocked rebind flags, `with _lock:` rebind doesn't
    glob = symbols(rep, "race-global-write")
    assert any("_other" in s for s in glob)
    assert not any("_plan" in s for s in glob)


# -- knobs --------------------------------------------------------------------


KNOBS_FIXTURE = {
    "common/config.py": """\
        import os

        FOO = os.environ.get("PIO_FIX_FOO", "7")
        BAZ = int(os.environ.get("PIO_FIX_BAZ", "5"))
        A = os.environ.get("PIO_FIX_DUP", "1")
        B = os.environ.get("PIO_FIX_DUP", "2")
    """,
    "docs/operations.md": """\
        # Ops

        | env var | default | meaning |
        |---|---|---|
        | `PIO_FIX_BAZ` | 6 | documented with the WRONG default |
        | `PIO_FIX_DUP` | 1 | read twice with different defaults |
        | `PIO_FIX_DEAD` | 1 | documented but read nowhere |
    """,
}


def test_knobs_contract_rules(tmp_path):
    root = make_repo(tmp_path, KNOBS_FIXTURE)
    rep = run(root, analyzers=["knobs"])
    assert symbols(rep, "knob-undocumented") == {"PIO_FIX_FOO"}
    assert symbols(rep, "knob-default-mismatch") == {"PIO_FIX_BAZ"}
    assert symbols(rep, "knob-inconsistent-default") == {"PIO_FIX_DUP"}
    assert symbols(rep, "knob-dead-doc") == {"PIO_FIX_DEAD"}
    knobs = rep.extras["knobs"]
    assert knobs["count"] == 3  # FOO, BAZ, DUP
    assert knobs["documented"] == 2
    entries = {e["name"]: e for e in knobs["entries"]}
    assert entries["PIO_FIX_BAZ"]["type"] == "int"
    assert entries["PIO_FIX_FOO"]["documented"] is False


# -- metrics ------------------------------------------------------------------


METRICS_FIXTURE = {
    "obs/m.py": """\
        def setup(reg):
            reg.counter("pio_fix_undoc_total", "d")
            reg.counter("pio_fix_typed_total", "d")
            reg.gauge("pio_fix_labeled", "d", ("user",))
            reg.counter("pio_fix_ok_total", "d", ("outcome",))
            reg.gauge("pio_fix_bad_name_total", "d")
    """,
    "docs/observability.md": r"""
        # Observability

        | metric | type | meaning |
        |---|---|---|
        | `pio_fix_typed_total` | gauge | wrong type on purpose |
        | `pio_fix_ok_total{outcome=hit\|miss}` | counter | labeled row parses |
        | `pio_fix_bad_name_total` | gauge | gauge named like a counter |
        | `pio_fix_dead_total` | counter | registered nowhere |
    """,
}


def test_metrics_contract_rules(tmp_path):
    root = make_repo(tmp_path, METRICS_FIXTURE)
    rep = run(root, analyzers=["metrics"])
    assert symbols(rep, "metric-undocumented") == {
        "pio_fix_undoc_total", "pio_fix_labeled",
    }
    assert symbols(rep, "metric-type-mismatch") == {"pio_fix_typed_total"}
    assert symbols(rep, "metric-dead-doc") == {"pio_fix_dead_total"}
    assert symbols(rep, "metric-label-cardinality") == {"pio_fix_labeled"}
    assert symbols(rep, "metric-naming") == {"pio_fix_bad_name_total"}
    # the catalog row with an inline label set (and an escaped pipe)
    # counts as documentation — pio_fix_ok_total is fully clean
    assert not any(f.symbol == "pio_fix_ok_total" for f in rep.findings)


# -- blocking -----------------------------------------------------------------


BLOCKING_FIXTURE = {
    "serving/batching.py": """\
        import json
        import time

        class Batcher:
            def dispatch(self, batch):
                time.sleep(0.001)
                return json.dumps(batch)

            def _wait(self, cv):
                cv.wait()
                return self.send(1)

            def send(self, x):
                return x
    """,
    "data/api/flusher.py": """\
        import time

        class Flusher:
            def _flush(self):
                time.sleep(0.01)

            def enqueue(self, x):
                time.sleep(0.01)  # not a hot-loop name: out of scope
                return x
    """,
    # ops/ivf.py is a dispatch module: probe selection runs per query,
    # while the publish-time k-means/recall/blob machinery is exempt
    "ops/ivf.py": """\
        import json
        import time

        def probe_select(q, centroids):
            time.sleep(0.001)
            return q

        def train_kmeans(v, nlist):
            time.sleep(0.01)  # publish-time: exempt
            return v

        def save_index(path, index):
            with open(path, "wb") as f:  # sealed-blob write: exempt
                f.write(json.dumps(index).encode())
    """,
    "ops/other_kernel.py": """\
        import time

        def launch(x):
            time.sleep(0.01)  # not a dispatch module: out of scope
            return x
    """,
}


def test_blocking_positives_and_negatives(tmp_path):
    root = make_repo(tmp_path, BLOCKING_FIXTURE)
    rep = run(root, analyzers=["blocking"])
    syms = symbols(rep, "blocking-call-in-hot-loop")
    assert syms == {"dispatch.sleep", "dispatch.dumps", "_flush.sleep",
                    "probe_select.sleep"}


DELTA_LOOP_FIXTURE = {
    # the event server's delta flush worker and the replica's catch-up
    # worker are hot-loop names: pacing belongs on Event.wait, real I/O
    # in delegated helpers
    "data/api/delta_flush.py": """\
        import json
        import time

        class Publisher:
            def _delta_loop(self):
                time.sleep(0.25)
                return json.dumps({"epoch": 1})

            def _flush_once(self):
                # delegated helper: not a hot-loop name, out of scope
                return json.dumps({"epoch": 1})
    """,
    "serving/delta_catchup.py": """\
        class Replica:
            def _catchup_loop(self):
                # repo idiom: pace on the sanctioned Event.wait and
                # delegate the actual log replay — must stay clean
                while not self._stop.is_set():
                    self._wake.wait(1.0)
                    self._wake.clear()
                    self._catch_up_once()

            def _catch_up_once(self):
                return 0
    """,
    "core/delta_worker.py": """\
        import time

        class Log:
            def _delta_loop(self):
                time.sleep(0.01)  # not serving//data/api: out of scope
    """,
}


def test_blocking_delta_worker_loops(tmp_path):
    root = make_repo(tmp_path, DELTA_LOOP_FIXTURE)
    rep = run(root, analyzers=["blocking"])
    syms = symbols(rep, "blocking-call-in-hot-loop")
    assert syms == {"_delta_loop.sleep", "_delta_loop.dumps"}


CANARY_LOOP_FIXTURE = {
    # the canary controller's verification window and post-promotion
    # soak watchdog are hot-loop names: pacing belongs on Event.wait,
    # every blocking step (HTTP probes, journal I/O) in tick helpers
    "serving/canary_bad.py": """\
        import json
        import time

        class Controller:
            def _verify_loop(self):
                time.sleep(0.25)
                return json.dumps({"state": "verifying"})

            def _soak_loop(self):
                time.sleep(0.25)
    """,
    "serving/canary_good.py": """\
        class Controller:
            def _verify_loop(self):
                # repo idiom: pace on the sanctioned Event.wait and
                # delegate the tick — must stay clean
                while not self._stop_evt.wait(self.tick_s):
                    if self._verify_tick():
                        return

            def _soak_loop(self):
                while not self._stop_evt.wait(self.tick_s):
                    if self._soak_tick():
                        return

            def _verify_tick(self):
                # delegated helper: not a hot-loop name, out of scope
                return True

            def _soak_tick(self):
                return True
    """,
    "core/canary_elsewhere.py": """\
        import time

        class Controller:
            def _verify_loop(self):
                time.sleep(0.25)  # not serving//data/api: out of scope
    """,
}


def test_blocking_canary_controller_loops(tmp_path):
    root = make_repo(tmp_path, CANARY_LOOP_FIXTURE)
    rep = run(root, analyzers=["blocking"])
    syms = symbols(rep, "blocking-call-in-hot-loop")
    assert syms == {"_verify_loop.sleep", "_verify_loop.dumps",
                    "_soak_loop.sleep"}


# -- lockorder ----------------------------------------------------------------


LOCKORDER_CYCLE_FIXTURE = {
    "serving/ab.py": """\
        import threading

        class Metrics:
            def __init__(self):
                self._m_lock = threading.Lock()

            def record(self):
                with self._m_lock:
                    return 1

            def snapshot(self, router: "Router"):
                with self._m_lock:
                    return router.peek()

        class Router:
            def __init__(self):
                self._lock = threading.Lock()
                self.metrics = Metrics()

            def forward(self):
                with self._lock:
                    return self.metrics.record()

            def peek(self):
                with self._lock:
                    return 0
    """,
}


def test_lockorder_detects_ab_ba_cycle_across_calls(tmp_path):
    root = make_repo(tmp_path, LOCKORDER_CYCLE_FIXTURE)
    rep = run(root, analyzers=["lockorder"])
    cyc = by_rule(rep, "lockorder-cycle")
    assert len(cyc) == 1
    f = cyc[0]
    assert "_m_lock" in f.symbol and "_lock" in f.symbol
    # the witness chains show BOTH sides of the inversion with file:line
    assert "one side:" in f.message and "other side:" in f.message
    assert "serving/ab.py:" in f.message


def test_lockorder_consistent_order_is_clean(tmp_path):
    root = make_repo(tmp_path, {
        "serving/ok.py": """\
            import threading

            class Inner:
                def __init__(self):
                    self._i_lock = threading.Lock()

                def work(self):
                    with self._i_lock:
                        return 1

            class Outer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.inner = Inner()

                def a(self):
                    with self._lock:
                        return self.inner.work()

                def b(self):
                    with self._lock:
                        with self.inner._i_lock:
                            return 2
        """,
    })
    rep = run(root, analyzers=["lockorder"])
    assert by_rule(rep, "lockorder-cycle") == []


def test_lockorder_report_carries_callgraph_stats(tmp_path):
    root = make_repo(tmp_path, LOCKORDER_CYCLE_FIXTURE)
    rep = run(root, analyzers=["lockorder"])
    stats = rep.extras["callgraph"]
    assert stats["nodes"] > 0 and stats["resolution_rate"] is not None


def test_cli_graph_lockorder_dumps_dot(tmp_path, capsys):
    from predictionio_tpu.tools.cli import main

    root = make_repo(tmp_path, LOCKORDER_CYCLE_FIXTURE)
    assert main(["analyze", "--root", root, "--graph", "lockorder"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("digraph lockorder")
    assert "color=red" in out  # cycle edges are highlighted


# -- deadline -----------------------------------------------------------------


DEADLINE_FIXTURE = {
    "serving/handler.py": """\
        import urllib.request

        def handle_query(req):
            return fetch_features(req)

        def fetch_features(req):
            # reachable hop with no deadline contract: must flag
            return urllib.request.urlopen("http://storage/find", timeout=5)

        def handle_retry(req, policy):
            return call_with_resilience(lambda: 1, policy)

        def handle_forward(req, headers):
            headers[DEADLINE_HEADER] = req.headers.get(DEADLINE_HEADER)
            return headers

        def metrics_loop():
            # NOT reachable from any request entry: control loops own
            # their timeouts
            return urllib.request.urlopen("http://self/stats", timeout=5)
    """,
    "serving/clean.py": """\
        import urllib.request

        def handle_good(req, deadline, policy, pool):
            headers = {}
            headers[DEADLINE_HEADER] = f"{deadline.remaining_ms():.0f}"
            urllib.request.urlopen("http://x/", timeout=1)
            call_with_resilience(lambda: 1, policy, deadline=deadline)
            pool.submit(work, deadline=deadline)
            return headers

        def handle_waived(req):
            # fire-and-forget by design
            # pio: ignore[deadline-drop]
            return urllib.request.urlopen("http://fire/forget", timeout=1)
    """,
}


def test_deadline_rules_positive_and_negative(tmp_path):
    root = make_repo(tmp_path, DEADLINE_FIXTURE)
    rep = run(root, analyzers=["deadline"])
    drops = symbols(rep, "deadline-drop")
    # flagged through the call chain (fetch_features has no request verb)
    assert drops == {"fetch_features"}
    assert symbols(rep, "deadline-not-forwarded") == {"handle_retry"}
    assert symbols(rep, "deadline-stale-forward") == {"handle_forward"}
    assert rep.suppressed == 1  # handle_waived


def test_deadline_submit_must_forward_in_hand_deadline(tmp_path):
    root = make_repo(tmp_path, {
        "serving/batch.py": """\
            def handle_batch(req, deadline, pool):
                return pool.submit(work, req)
        """,
    })
    rep = run(root, analyzers=["deadline"])
    assert symbols(rep, "deadline-not-forwarded") == {"handle_batch.submit"}


DELTA_DEADLINE_FIXTURE = {
    # the streaming delta plane: push_delta (router propagation hop)
    # and catchup (replica log-replay worker) are request entry verbs
    "serving/delta_push.py": """\
        import urllib.request

        def push_delta(payload):
            # outbound hop with no deadline contract: must flag
            return urllib.request.urlopen("http://replica/delta", timeout=5)

        def push_delta_fenced(payload, deadline):
            headers = {}
            headers[DEADLINE_HEADER] = f"{deadline.remaining_ms():.0f}"
            return urllib.request.urlopen("http://replica/delta", timeout=1)
    """,
    "serving/delta_catchup.py": """\
        import urllib.request

        def catchup_from_log(url):
            # catch-up fetch without the contract: must flag
            return urllib.request.urlopen(url, timeout=5)
    """,
    "core/delta_core.py": """\
        import urllib.request

        def push_delta_local(payload):
            # not a serving/data layer: control plane, out of scope
            return urllib.request.urlopen("http://x/", timeout=5)
    """,
}


def test_deadline_delta_plane_entry_points(tmp_path):
    root = make_repo(tmp_path, DELTA_DEADLINE_FIXTURE)
    rep = run(root, analyzers=["deadline"])
    drops = symbols(rep, "deadline-drop")
    assert drops == {"push_delta", "catchup_from_log"}


CANARY_SHADOW_DEADLINE_FIXTURE = {
    # the canary's shadow-mirror hop replays captured queries to
    # candidate + baseline; it is a "serve" request verb and must carry
    # the remaining budget downstream like any other hop
    "serving/canary_shadow.py": """\
        import urllib.request

        def _serve_shadow_pair(body, url):
            # repo idiom: a fresh per-mirror deadline, remaining budget
            # forwarded on the wire — must stay clean
            deadline = Deadline.after_ms(1000.0)
            headers = {}
            headers[DEADLINE_HEADER] = f"{deadline.remaining_ms():.0f}"
            return urllib.request.urlopen(url, timeout=1)

        def serve_shadow_dropped(body, url):
            # mirrored hop with no deadline contract: must flag
            return urllib.request.urlopen(url, timeout=1)
    """,
}


def test_deadline_canary_shadow_hop(tmp_path):
    root = make_repo(tmp_path, CANARY_SHADOW_DEADLINE_FIXTURE)
    rep = run(root, analyzers=["deadline"])
    assert symbols(rep, "deadline-drop") == {"serve_shadow_dropped"}
    assert not any(f.symbol == "_serve_shadow_pair" for f in rep.findings)


# -- collective ---------------------------------------------------------------


COLLECTIVE_FIXTURE = {
    "parallel/dev.py": """\
        import jax
        from jax.sharding import PartitionSpec as P

        def run_bad_mesh(xs):
            mesh = make_mesh(axes={"data": 2})
            f = shard_map(body, mesh=mesh, in_specs=(P("model"),),
                          out_specs=P("model"))
            return f(xs)

        def run_bad_collective(xs, mesh):
            def body(x):
                return jax.lax.psum(x, "model")
            f = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
            return f(xs)

        def run_clean(xs, mesh):
            def body(x):
                return jax.lax.psum(x, "data")
            f = shard_map(body, mesh=mesh, in_specs=(P("data"),),
                          out_specs=P("data"))
            return f(xs)

        def run_dynamic_axis(xs, mesh, axis):
            def body(x):
                return jax.lax.psum(x, axis)
            f = shard_map(body, mesh=mesh, in_specs=(P(axis),),
                          out_specs=P(axis))
            return f(xs)
    """,
    "ops/kern.py": """\
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def launch_bad(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 4),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
            )(x)

        def helper_syncs(v, n):
            if v > 0:
                return v.item()
            return n

        def helper_clean(v, n):
            if v is None:
                return n
            return v + n

        @jax.jit
        def traced(x):
            a = helper_syncs(x, 3)
            b = helper_clean(x, 4)
            return a + b
    """,
}


def test_collective_rules_positive_and_negative(tmp_path):
    root = make_repo(tmp_path, COLLECTIVE_FIXTURE)
    rep = run(root, analyzers=["collective"])
    assert symbols(rep, "collective-mesh-axis") == {"model"}
    assert symbols(rep, "collective-unknown-axis") == {"model"}
    # dynamic axis names and param meshes are skipped, never guessed
    assert not any(
        "run_dynamic_axis" in f.message or "run_clean" in f.message
        for f in rep.findings
    )
    arity = by_rule(rep, "collective-index-map-arity")
    assert len(arity) == 1  # the 1-arg lambda; the 2-arg one is fine
    assert "grid is rank 2" in arity[0].message
    host = symbols(rep, "collective-host-in-callee")
    # .item() and the value branch inside the callee, but NOT the
    # `is None` identity check in helper_clean
    assert any("helper_syncs" in s for s in host)
    assert not any("helper_clean" in s for s in host)


POD_COLLECTIVE_FIXTURE = {
    "serving/pod.py": """\
        import jax
        from jax.sharding import PartitionSpec as P

        def pod_clean(xs, ctx):
            sc = ctx.pod_submesh(4, 2)
            def body(v, g):
                return two_tier_merge_topk(
                    v, g, 10, group_axis="data", host_axis="host")
            f = shard_map(body, mesh=sc.mesh,
                          in_specs=(P(("host", "data"), None),
                                    P(("host", "data"), None)),
                          out_specs=(P(), P()))
            return f(xs, xs)

        def pod_bad_mesh(xs, ctx):
            sc = ctx.pod_submesh(4, 2)
            def body(v):
                return jax.lax.psum(v, "model")
            f = shard_map(body, mesh=sc.mesh, in_specs=(P("model"),),
                          out_specs=P("model"))
            return f(xs)

        def pod_bad_tier_axis(xs, mesh):
            def body(v, g):
                return two_tier_merge_topk(
                    v, g, 10, group_axis="data", host_axis="ring")
            f = shard_map(body, mesh=mesh,
                          in_specs=(P(("host", "data"), None),
                                    P(("host", "data"), None)),
                          out_specs=(P(), P()))
            return f(xs, xs)

        def pod_degenerate(v, g):
            return two_tier_merge_topk(
                v, g, 10, group_axis="data", host_axis="data")

        def pod_dynamic(v, g, ax):
            return two_tier_merge_topk(v, g, 10, group_axis=ax,
                                       host_axis=ax)
    """,
}


def test_collective_pod_two_tier_rules(tmp_path):
    root = make_repo(tmp_path, POD_COLLECTIVE_FIXTURE)
    rep = run(root, analyzers=["collective"])
    # pod_submesh meshes resolve to {host, data}: the spec axis "model"
    # in pod_bad_mesh is flagged against them
    assert symbols(rep, "collective-mesh-axis") == {"model"}
    # two_tier_merge_topk's axis kwargs are collective axis uses: the
    # unbound "ring" is caught, the in-scope pod_clean call is not
    assert symbols(rep, "collective-unknown-axis") == {"ring"}
    # group_axis == host_axis collapses the two tiers onto one axis
    degen = by_rule(rep, "collective-two-tier-axes")
    assert [f.symbol for f in degen] == ["data"]
    # dynamic axis params are skipped, never guessed
    assert not any(
        f.line and "pod_dynamic" in f.message for f in rep.findings
    )
    assert not any("pod_clean" in f.message for f in rep.findings)


# -- races: explicit acquire()/release() --------------------------------------


def test_races_acquire_release_pairs(tmp_path):
    root = make_repo(tmp_path, {
        "serving/explicit.py": """\
            import threading

            class Explicit:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    self._lock.acquire()
                    try:
                        self._n += 1
                    finally:
                        self._lock.release()

                def read(self):
                    with self._lock:
                        return self._n

            class Leaky:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    self._lock.acquire()
                    self._lock.release()
                    self._n += 1

                def read(self):
                    with self._lock:
                        return self._n
        """,
    })
    rep = run(root, analyzers=["races"])
    rmw = symbols(rep, "race-unguarded-rmw")
    # try/finally acquire() guards the write: clean
    assert not any("Explicit" in s for s in rmw)
    # a write AFTER release() is still unguarded: flagged
    assert any("Leaky" in s for s in rmw)


# -- baseline hygiene ---------------------------------------------------------


def test_stale_baseline_entries_warn_not_drop(tmp_path):
    root = make_repo(tmp_path, {"a.py": "import os\n"})
    stale_keys = [
        "hygiene-unused-import:a.py:os",        # live: resolves
        "nope-rule:a.py:os",                    # unknown rule
        "hygiene-unused-import:gone.py:os",     # missing file
        "hygiene-unused-import:a.py:vanished",  # symbol gone
    ]
    base = os.path.join(root, BASELINE_NAME)
    with open(base, "w") as f:
        json.dump({"version": 1, "findings": stale_keys}, f)
    rep = run(root, analyzers=["hygiene"])
    assert rep.baselined == 1
    stale = by_rule(rep, "baseline-stale")
    assert {s.symbol for s in stale} == set(stale_keys[1:])
    assert all(s.severity == "warning" for s in stale)


def test_cli_prune_baseline(tmp_path, capsys):
    from predictionio_tpu.tools.cli import main

    root = make_repo(tmp_path, {"a.py": "import os\n"})
    base = os.path.join(root, BASELINE_NAME)
    with open(base, "w") as f:
        json.dump({"version": 1, "findings": [
            "hygiene-unused-import:a.py:os",
            "nope-rule:a.py:os",
        ]}, f)
    assert main(["analyze", "--root", root, "--prune-baseline"]) == 0
    out = capsys.readouterr().out
    assert "nope-rule:a.py:os" in out and "1 stale entry pruned" in out
    assert load_baseline(base) == {"hygiene-unused-import:a.py:os"}
    # idempotent: nothing left to prune
    assert main(["analyze", "--root", root, "--prune-baseline"]) == 0


# -- SARIF --------------------------------------------------------------------


def test_cli_analyze_sarif(tmp_path, capsys):
    from predictionio_tpu.tools.cli import main

    root = make_repo(tmp_path, {"a.py": "import os\n"})
    code = main(["analyze", "--root", root, "--format", "sarif"])
    d = json.loads(capsys.readouterr().out)
    assert code == 1
    assert d["version"] == "2.1.0"
    run0 = d["runs"][0]
    assert run0["tool"]["driver"]["name"] == "pio-analyze"
    rule_ids = {r["id"] for r in run0["tool"]["driver"]["rules"]}
    assert "hygiene-unused-import" in rule_ids
    res = run0["results"][0]
    assert res["ruleId"] == "hygiene-unused-import"
    assert res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "a.py"
    assert loc["region"]["startLine"] >= 1
    assert res["partialFingerprints"]["pioKey"].startswith(
        "hygiene-unused-import:a.py:"
    )


def test_report_by_analyzer_counts(tmp_path):
    root = make_repo(tmp_path, {"a.py": "import os\n"})
    d = run(root, analyzers=["hygiene"]).to_dict()
    assert d["by_analyzer"]["hygiene"]["error"] == 1


# -- the real checkout --------------------------------------------------------


@pytest.fixture(scope="module")
def repo_report():
    return run(ROOT)


def test_repo_analyzes_clean(repo_report):
    errs = [f.render() for f in repo_report.findings
            if f.severity == "error"]
    assert repo_report.errors == 0, "\n".join(errs)


def test_the_serving_path_waives_no_block_sync_any_more():
    """ISSUE 38: a dispatch's ONE wait for the device is the `device_get`
    of the readback it queued at launch, inside its `device_compute` stage.
    The two `hotpath-block-sync` waivers on the scorers' `block_until_ready`
    went with that call; none was added elsewhere (`models/als.py` keeps the
    trainer's two).  ISSUE 45: that sequence is written once, in
    `RungPrograms.run`, and neither scorer's module names its parts."""
    import inspect

    from predictionio_tpu.serving import fastpath, rungs, seqpath

    waiver = "pio: ignore[hotpath-block-sync]"
    pkg = os.path.join(ROOT, "predictionio_tpu")
    counts = {}
    for dirpath, _, files in os.walk(pkg):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(dirpath, name)) as f:
                    n = f.read().count(waiver)
                if n:
                    counts[os.path.relpath(
                        os.path.join(dirpath, name), pkg)] = n
    assert counts == {os.path.join("models", "als.py"): 2}
    body = inspect.getsource(rungs.RungPrograms.run)
    assert "block_until_ready(" not in body
    # the record is told, then inside the stage: launch, the copy asked
    # for, the get that waits
    assert body.index("disp.rung") < body.index(
        '_tracing.stage("device_compute")') < body.index(
        "_tracing.launch()") < body.index("self._request(") < body.index(
        "jax.device_get(")
    assert "copy_to_host_async()" in inspect.getsource(
        rungs.RungPrograms._request)
    for mod in (fastpath, seqpath):
        text = inspect.getsource(mod)
        for name in ("device_get", "copy_to_host_async", "launch_gate",
                     "block_until_ready", "_tracing.launch"):
            assert name not in text, (mod.__name__, name)


def test_repo_knob_registry_is_fully_documented(repo_report):
    knobs = repo_report.extras["knobs"]
    undocumented = [e["name"] for e in knobs["entries"]
                    if not e["documented"]]
    assert knobs["count"] == knobs["documented"], undocumented
    assert knobs["count"] > 0


def test_repo_metric_catalog_is_fully_documented(repo_report):
    metrics = repo_report.extras["metrics"]
    assert metrics["count"] == metrics["documented"]
    assert metrics["count"] > 0


def test_repo_baseline_keys_all_load(repo_report):
    keys = load_baseline(os.path.join(ROOT, BASELINE_NAME))
    assert all(isinstance(k, str) and k.count(":") >= 2 for k in keys)


# -- CLI ----------------------------------------------------------------------


def test_cli_analyze_json(tmp_path, capsys):
    from predictionio_tpu.tools.cli import main

    root = make_repo(tmp_path, {"a.py": "import os\n"})
    code = main(["analyze", "--format", "json", "--root", root])
    d = json.loads(capsys.readouterr().out)
    assert code == 1  # unused import is an error
    assert d["counts"]["error"] == 1
    assert d["findings"][0]["rule"] == "hygiene-unused-import"


def test_cli_analyze_write_baseline_then_clean(tmp_path, capsys):
    from predictionio_tpu.tools.cli import main

    root = make_repo(tmp_path, {"a.py": "import os\n"})
    assert main(["analyze", "--root", root, "--write-baseline"]) == 0
    capsys.readouterr()
    assert main(["analyze", "--root", root]) == 0
    out = capsys.readouterr().out
    assert "1 baselined" in out


def test_cli_list_rules(capsys):
    from predictionio_tpu.tools.cli import main

    assert main(["analyze", "--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("hotpath-host-sync", "race-unguarded-rmw",
                "knob-undocumented", "metric-undocumented",
                "blocking-call-in-hot-loop", "hygiene-unused-import"):
        assert rid in out
