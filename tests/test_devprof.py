"""Device-utilization accountant + slow-request flight recorder units.

ISSUE 8 acceptance at the unit level: the cost models match the formulas
their docstrings state, the rolling-window accountant reports real rates
(and ages records out), the tail sampler never judges a request against
itself, and — the invariant the flight recorder exists to protect —
device time is charged once per dispatch, never to coalesced followers.
"""

import threading
import time

import pytest

from predictionio_tpu.common.resilience import Deadline, DeadlineExceeded
from predictionio_tpu.obs import devprof
from predictionio_tpu.obs import tracing as obs_tracing
from predictionio_tpu.obs.tracing import Trace, Tracer
from predictionio_tpu.serving.batching import MicroBatcher

V5E = "TPU v5 lite"  # the one device_kind in devprof.PEAKS


# -- cost models --------------------------------------------------------------


class TestCostModels:
    def test_peaks_keyed_by_device_kind(self):
        assert devprof.peak_for("TPU v5 lite")["flops"] == 197e12
        assert devprof.peak_for("TPU v5 lite")["hbm_gbps"] == 819e9
        # a platform name is not a device: no TPU borrows v5e's peaks,
        # and the CPU has no row at all
        for kind in ("tpu", "TPU v4", "TPU v6 lite", "cpu", "rocm", None):
            assert devprof.peak_for(kind) is None

    def test_als_train_cost_matches_published_formula(self):
        k, nr, nu, ni = 8, 1000, 50, 40
        flops, nbytes = devprof.als_train_cost(nr, nu, ni, k)
        ents = nu + ni
        assert flops == nr * 2 * (2 * k * k + 4 * k) * 2 + ents * (
            2 * k**3 / 3
        )
        assert nbytes == nr * 2 * (k * 4 + 12) + ents * k * (4 + 4)

    def test_bf16_halves_factor_bytes_not_flops(self):
        f32 = devprof.als_train_cost(1000, 50, 40, 8, "f32")
        bf16 = devprof.als_train_cost(1000, 50, 40, 8, "bf16")
        assert bf16[0] == f32[0]
        assert bf16[1] < f32[1]

    def test_fused_train_cost_matches_published_formula(self):
        k, nr, nu, ni = 10, 1000, 50, 40
        for cd in ("f32", "bf16", "int8"):
            flops, nbytes = devprof.fused_train_cost(nr, nu, ni, k, cd)
            assert flops == devprof.als_train_cost(nr, nu, ni, k)[0]
            assert nbytes == (
                nr * 2 * 12.0
                + devprof.fused_train_vread_bytes(nu, ni, k, cd)
                + (nu + ni) * k * 4.0
            )

    def test_fused_vread_int8_at_most_half_of_f32(self):
        f32 = devprof.fused_train_vread_bytes(162_000, 59_000, 10, "f32")
        int8 = devprof.fused_train_vread_bytes(162_000, 59_000, 10, "int8")
        assert f32 == (162_000 + 59_000) * 10 * 4.0
        assert int8 == (162_000 + 59_000) * (10 * 1.0 + 4.0)  # +scale col
        assert int8 <= 0.5 * f32

    def test_score_cost_scales_with_batch_and_items(self):
        f1, b1 = devprof.score_cost(1, 400, 8)
        f16, b16 = devprof.score_cost(16, 400, 8)
        assert f16 == 16 * f1  # matmul flops linear in batch rows
        assert b16 > b1
        assert f1 > 0 and b1 > 0


# -- rolling-window accountant ------------------------------------------------


class TestDeviceUtilization:
    def test_snapshot_none_before_first_dispatch(self):
        acc = devprof.DeviceUtilization(device_kind="cpu")
        acc.set_cost("b8", 1e6, 2e6)
        assert acc.snapshot() is None

    def test_snapshot_rates_and_utilization(self):
        acc = devprof.DeviceUtilization(device_kind=V5E, window_s=60)
        acc.set_cost("b8", 1e6, 2e6, source="analytic")
        acc.record("b8", 0.002)
        acc.record("b8", 0.003)
        snap = acc.snapshot()
        assert snap["device_kind"] == V5E
        assert snap["dispatches_window"] == 2
        assert snap["dispatches_total"] == 2
        assert snap["busy_s"] == pytest.approx(0.005)
        assert 0.0 < snap["busy_fraction"] <= 1.0
        assert snap["flops_per_s"] > 0 and snap["hbm_gbps"] > 0
        # v5e is in the table, so utilization is a real number, not null
        assert snap["mfu"] is not None and snap["mfu"] > 0
        assert snap["hbm_util"] is not None and snap["hbm_util"] > 0
        assert acc.costs()["b8"]["source"] == "analytic"

    def test_unlisted_device_reports_null_utilization(self):
        acc = devprof.DeviceUtilization(device_kind="cpu", window_s=60)
        acc.set_cost("b", 1e6, 1e6)
        acc.record("b", 0.001)
        snap = acc.snapshot()
        assert snap["mfu"] is None and snap["hbm_util"] is None
        assert snap["flops_per_s"] > 0  # rates still real

    def test_uncosted_dispatch_counts_but_adds_no_flops(self):
        acc = devprof.DeviceUtilization(device_kind="cpu", window_s=60)
        acc.record("never_annotated", 0.001)
        snap = acc.snapshot()
        assert snap["dispatches_total"] == 1
        assert snap["flops_per_s"] == 0.0
        assert snap["busy_s"] == pytest.approx(0.001)

    def test_window_ages_records_out(self):
        acc = devprof.DeviceUtilization(device_kind="cpu", window_s=60)
        acc.set_cost("b", 1e6, 1e6)
        acc.record("b", 0.001)
        acc.record("b", 0.001)
        # age the first record past the window (white-box: avoids a
        # 60-second sleep); lifetime counter must survive the prune
        t, s, f, by = acc._records[0]
        acc._records[0] = (t - 120.0, s, f, by)
        snap = acc.snapshot()
        assert snap["dispatches_window"] == 1
        assert snap["dispatches_total"] == 2

    def test_negative_wall_clamped(self):
        acc = devprof.DeviceUtilization(device_kind="cpu", window_s=60)
        acc.record("b", -1.0)
        assert acc.snapshot()["busy_s"] == 0.0

    def test_busy_fraction_clamped_at_one(self):
        acc = devprof.DeviceUtilization(device_kind="cpu", window_s=60)
        acc.record("b", 100.0)  # more busy than elapsed: clamp, not >1
        assert acc.snapshot()["busy_fraction"] == 1.0

    def test_window_env_knob(self, monkeypatch):
        monkeypatch.setenv("PIO_DEVPROF_WINDOW", "7")
        assert devprof.DeviceUtilization().window_s == 7.0


class TestTrainRecorder:
    @pytest.fixture(autouse=True)
    def _reset_global(self, monkeypatch):
        monkeypatch.setattr(devprof, "_train_acc", None)

    def test_process_global_reuse(self):
        a = devprof.train_recorder(device_kind="cpu")
        assert devprof.train_recorder() is a
        assert devprof.train_recorder(device_kind="cpu") is a

    def test_device_change_recreates(self):
        a = devprof.train_recorder(device_kind="cpu")
        b = devprof.train_recorder(device_kind=V5E)
        assert b is not a and b.device_kind == V5E

    def test_train_snapshot(self):
        assert devprof.train_snapshot() is None
        acc = devprof.train_recorder(device_kind="cpu")
        acc.set_cost("step", 1e6, 1e6)
        acc.record("step", 0.001)
        assert devprof.train_snapshot()["dispatches_total"] == 1


# -- tail-sampling flight recorder --------------------------------------------


def _finished(wall_s: float, rid: str = "") -> Trace:
    tr = Trace(rid or obs_tracing.new_request_id(), "q")
    tr.wall_s = wall_s  # deterministic wall instead of sleeping
    tr.stages["other"] = wall_s
    return tr


class TestSlowFlightRecorder:
    def test_nothing_retained_before_min_samples(self):
        t = Tracer(sample_rate=1.0, slow_quantile=0.5, slow_ring_size=8)
        for _ in range(obs_tracing._SLOW_MIN_SAMPLES - 1):
            t.record(_finished(0.001))
        assert t.slow_threshold_s() is None  # reservoir still cold
        t.record(_finished(10.0))  # an outlier, but judged while cold
        assert t.slow_retained == 0

    def test_outlier_retained_after_warmup(self):
        t = Tracer(sample_rate=1.0, slow_quantile=0.9, slow_ring_size=8)
        for _ in range(32):
            t.record(_finished(0.001))
        assert t.slow_threshold_s() == pytest.approx(0.001)
        t.record(_finished(0.5, rid="slowone"))
        assert t.slow_retained == 1
        assert t.slow_recent()[0]["requestId"] == "slowone"
        # a typical request is NOT retained
        t.record(_finished(0.001))
        assert t.slow_retained == 1

    def test_threshold_excludes_current_wall(self):
        """The first outlier after warmup must be judged against the walls
        BEFORE it — if its own wall entered the quantile first, a regime
        shift's first slow request could raise the bar over itself."""
        t = Tracer(sample_rate=1.0, slow_quantile=0.99, slow_ring_size=8)
        # exactly one recompute boundary away: the outlier lands right
        # after a recompute, so a buggy admit-then-judge would use a
        # threshold containing the 10s wall
        for _ in range(obs_tracing._SLOW_RECOMPUTE * 2):
            t.record(_finished(0.001))
        t.record(_finished(10.0))
        assert t.slow_retained == 1

    def test_quantile_zero_disables(self):
        t = Tracer(sample_rate=1.0, slow_quantile=0.0, slow_ring_size=8)
        for _ in range(64):
            t.record(_finished(0.001))
        t.record(_finished(10.0))
        assert t.slow_retained == 0
        assert len(t._walls) == 0  # no reservoir work either

    def test_slow_ring_bounded(self):
        t = Tracer(sample_rate=1.0, slow_quantile=0.5, slow_ring_size=3)
        for _ in range(32):
            t.record(_finished(0.001))
        for i in range(10):
            t.record(_finished(1.0 + i))
        assert t.slow_retained >= 3  # lifetime counter keeps counting
        assert len(t.slow_ring) == 3  # ring stays bounded

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("PIO_SLOW_TRACE_QUANTILE", "0.5")
        monkeypatch.setenv("PIO_SLOW_TRACE_RING", "5")
        t = Tracer(sample_rate=1.0)
        assert t.slow_quantile == 0.5 and t.slow_ring_max == 5


# -- device time charged once per dispatch (satellite 3) ----------------------


class TestDeviceChargedOncePerDispatch:
    def test_coalesced_follower_trace_carries_no_device_stages(self):
        """A follower rides the leader's device slot: its trace must show
        the wait, the ``coalesce=follower`` context, and NO device stages
        — while still reconciling stage sum ≡ wall via ``other``."""
        started = threading.Event()
        release = threading.Event()
        calls = []

        def run_batch(queries):
            calls.append(len(queries))
            started.set()
            # hold the leader in flight so the follower provably attaches
            assert release.wait(5.0)
            with obs_tracing.stage("device_compute"):
                time.sleep(0.001)
            return [f"r:{q}" for q in queries]

        mb = MicroBatcher(run_batch, max_batch=4)
        tracer = Tracer(sample_rate=1.0, slow_quantile=0.0)
        results = {}

        def submit(role):
            tr = tracer.begin(role, "q")
            with obs_tracing.scope((tr,)):
                results[role] = mb.submit("same-query", key="k1")
            tr.finish(200)
            tracer.record(tr)

        try:
            t_leader = threading.Thread(target=submit, args=("leader",))
            t_leader.start()
            assert started.wait(5.0)
            t_follower = threading.Thread(
                target=submit, args=("follower",)
            )
            t_follower.start()
            # follower must be attached to the in-flight leader before the
            # batch is released, or it would lead its own dispatch
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with mb._key_lock:
                    leader_p = mb._inflight_keys.get("k1")
                    if leader_p is not None and leader_p.followers:
                        break
                time.sleep(0.005)
            release.set()
            t_leader.join(5.0)
            t_follower.join(5.0)
        finally:
            release.set()
            mb.stop()

        assert results["leader"] == results["follower"] == "r:same-query"
        assert calls == [1]  # ONE device dispatch for two requests
        by_id = {t["requestId"]: t for t in tracer.recent()}
        leader, follower = by_id["leader"], by_id["follower"]
        assert "device_compute" in leader["stagesMs"]
        assert leader["meta"]["coalesce"] == "leader"
        # the invariant: no device stage ever lands on a follower
        for stage in ("device_compute", "h2d", "batch_assembly"):
            assert stage not in follower["stagesMs"], follower
        assert follower["meta"]["coalesce"] == "follower"
        for tr in (leader, follower):
            assert sum(tr["stagesMs"].values()) == pytest.approx(
                tr["wallMs"], abs=0.05
            )

    def test_follower_never_reaches_run_batch(self):
        """stats-level view of the same invariant: coalesced counter up,
        batch counter charged once."""
        release = threading.Event()
        started = threading.Event()

        def run_batch(queries):
            started.set()
            assert release.wait(5.0)
            return list(queries)

        mb = MicroBatcher(run_batch, max_batch=4)
        try:
            threads = [
                threading.Thread(
                    target=lambda: mb.submit("q", key="same")
                )
                for _ in range(3)
            ]
            threads[0].start()
            assert started.wait(5.0)
            for t in threads[1:]:
                t.start()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with mb._key_lock:
                    p = mb._inflight_keys.get("same")
                    if p is not None and len(p.followers) == 2:
                        break
                time.sleep(0.005)
            release.set()
            for t in threads:
                t.join(5.0)
            stats = mb.stats()
            assert stats["coalesced"] == 2
            assert stats["queries"] == 1  # device saw ONE query
        finally:
            release.set()
            mb.stop()

    def test_promoted_follower_charged_once_leader_charged_never(self):
        """A leader hedged away (deadline lapsed in queue, e.g. because the
        router's hedge already answered elsewhere) must not be charged for
        device stages — the promoted follower takes the batch slot and the
        device bill, exactly once, with ``promoted=True`` recording why."""
        started = threading.Event()
        release = threading.Event()
        calls = []

        def run_batch(queries):
            calls.append(list(queries))
            if len(calls) == 1:
                # first dispatch: an unrelated blocker that pins the worker
                # so the keyed leader stays queued past its deadline
                started.set()
                assert release.wait(5.0)
            else:
                with obs_tracing.stage("device_compute"):
                    time.sleep(0.001)
            return [f"r:{q}" for q in queries]

        mb = MicroBatcher(run_batch, max_batch=4)
        tracer = Tracer(sample_rate=1.0, slow_quantile=0.0)
        results = {}

        def submit(role, query, key, deadline):
            tr = tracer.begin(role, query)
            try:
                with obs_tracing.scope((tr,)):
                    results[role] = mb.submit(
                        query, key=key, deadline=deadline
                    )
                tr.finish(200)
            except DeadlineExceeded as e:
                results[role] = e
                tr.finish(504)
            tracer.record(tr)

        try:
            t_blocker = threading.Thread(
                target=submit, args=("blocker", "other", None, None)
            )
            t_blocker.start()
            assert started.wait(5.0)  # worker now pinned in flight
            t_leader = threading.Thread(
                target=submit,
                args=("leader", "same-query", "k1", Deadline.after_ms(150)),
            )
            t_leader.start()
            # leader must be the registered (queued) coalescing leader
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with mb._key_lock:
                    if mb._inflight_keys.get("k1") is not None:
                        break
                time.sleep(0.005)
            t_follower = threading.Thread(
                target=submit,
                args=("follower", "same-query", "k1", Deadline.after_ms(5000)),
            )
            t_follower.start()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with mb._key_lock:
                    p = mb._inflight_keys.get("k1")
                    if p is not None and p.followers:
                        break
                time.sleep(0.005)
            t_leader.join(5.0)  # leader gives up at its 150 ms deadline
            release.set()  # NOW the worker reaches the expired leader
            t_blocker.join(5.0)
            t_follower.join(5.0)
        finally:
            release.set()
            mb.stop()

        assert isinstance(results["leader"], DeadlineExceeded)
        assert results["follower"] == "r:same-query"
        # the promoted follower's dispatch carried ONE copy of the query
        assert calls[1:] == [["same-query"]]
        by_id = {t["requestId"]: t for t in tracer.recent()}
        leader, follower = by_id["leader"], by_id["follower"]
        # device charged exactly once: to the promoted follower, which is
        # the leader at dispatch time and says so
        assert "device_compute" in follower["stagesMs"]
        assert follower["meta"]["coalesce"] == "leader"
        assert follower["meta"]["promoted"] is True
        # ...and never to the abandoned leader
        for stage in ("device_compute", "h2d", "batch_assembly"):
            assert stage not in leader["stagesMs"], leader
        assert "promoted" not in leader.get("meta", {})
