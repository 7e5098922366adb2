"""The seven per-layer metrics that read the batcher's dispatch records
(PR 25): each reader on hand-made input, on a program that lacks the spans
and counters (the parent), in a CPU rehearsal of the whole command, and
`idle.named_share` on a slice recorded on the chip."""

import json
import os
import sys
import time

import pytest
import run as bench_run

from pio_bench import xplane
from pio_bench.readers import load_reader

NEW = ("batch.passes_per_request", "batch.turnaround_ms",
       "batch.carried_share", "batch.inline_share", "batch.run_max_ms",
       "fastpath.d2h_ms", "idle.named_share")
SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "wgde-d128.serve-steady.pio-spans.xplane.pb")


def ctx_of(before, after, traces=()):
    return {"counters_before": {"batcher." + k: v for k, v in before.items()},
            "counters_after": {"batcher." + k: v for k, v in after.items()},
            "traces": list(traces), "device_trace": {}}


def trace(status=200, passes=None, d2h=None):
    t = {"status": status, "stagesMs": {"h2d": 1.0, "other": 0.5}}
    if passes is not None:
        t["meta"] = {"passes": passes, "dispatch_seq": 7}
    if d2h is not None:
        t["stagesMs"]["d2h"] = d2h
    return t


def test_the_counter_readers_take_deltas_over_the_window():
    ctx = ctx_of(
        {"batches": 10, "queries": 40, "inline_batches": 2, "carried_rows": 5,
         "turnaround_ms_sum": 30.0, "turnaround_n": 6, "run_ms_max": 250.0,
         "run_ms_max_seq": 4},
        {"batches": 110, "queries": 1640, "inline_batches": 11,
         "carried_rows": 645, "turnaround_ms_sum": 730.0, "turnaround_n": 96,
         "run_ms_max": 281.5, "run_ms_max_seq": 57})
    assert load_reader("batch.turnaround_ms")(ctx) == pytest.approx(700 / 90)
    assert load_reader("batch.carried_share")(ctx) == pytest.approx(40.0)
    assert load_reader("batch.inline_share")(ctx) == pytest.approx(9.0)
    assert load_reader("batch.run_max_ms")(ctx) == 281.5


def test_a_maximum_set_before_the_window_is_not_the_windows():
    ctx = ctx_of({"batches": 10, "run_ms_max": 900.0, "run_ms_max_seq": 4},
                 {"batches": 110, "run_ms_max": 900.0, "run_ms_max_seq": 4})
    assert load_reader("batch.run_max_ms")(ctx) is None


def test_the_span_readers_take_answered_requests_that_carry_the_span():
    traces = [trace(passes=1, d2h=1.0), trace(passes=2, d2h=2.0),
              trace(passes=3, d2h=9.0), trace(status=503, passes=9, d2h=99.0),
              trace()]  # a cache hit: no dispatch, no readback
    ctx = ctx_of({}, {}, traces)
    assert load_reader("batch.passes_per_request")(ctx) == pytest.approx(2.0)
    assert load_reader("fastpath.d2h_ms")(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    """The parent commit: `inline_batches` is all it has."""
    ctx = ctx_of({"batches": 10, "queries": 40, "inline_batches": 2},
                 {"batches": 110, "queries": 1640, "inline_batches": 11},
                 [trace(), trace()])
    value = load_reader(name)(ctx)
    if name == "batch.inline_share":
        assert value == pytest.approx(9.0)
    else:
        assert value is None


def named(planes):
    return load_reader("idle.named_share").__globals__["named_share"](planes)


def test_named_share_is_idle_time_under_a_pio_span_over_all_idle_time():
    dev = {xplane.OPS_LINE: [("op", 0.0, 100.0), ("op", 150.0, 50.0),
                             ("op", 300.0, 100.0), ("op", 410.0, 10.0)]}
    # idle: 100-150, 200-300, 400-410 = 160 ns; the spans cover 120-150,
    # 200-260 (two spans that overlap count once) and nothing of the last
    host = {"python3": [("pio.h2d", 120.0, 50.0), ("pio.collect", 190.0, 40.0),
                        ("pio.d2h", 220.0, 40.0), ("shard_args", 400.0, 10.0)]}
    planes = {"/device:TPU:0": dev, "/host:CPU": host}
    assert named(planes) == pytest.approx(100.0 * (30 + 60) / 160)
    # no span of the program's in the trace: nothing to read
    host_only_jax = {"python3": [("shard_args", 120.0, 50.0)]}
    assert named({"/device:TPU:0": dev, "/host:CPU": host_only_jax}) is None
    assert load_reader("idle.named_share")({"device_trace": {}}) is None


@pytest.mark.skipif(not os.path.exists(SLICE),
                    reason="the recorded slice is not in this checkout")
def test_named_share_on_a_slice_recorded_on_the_chip():
    """A slice of `wgde-d128.serve-steady` on one v5e chip (PR 25)."""
    planes = xplane.load(SLICE)
    spans = {n for lines in planes["/host:CPU"].values()
             for n, _, _ in lines if n.startswith("pio.")}
    assert {"pio.collect", "pio.h2d", "pio.device_compute", "pio.d2h",
            "pio.resolve"} <= spans
    assert named(planes) == pytest.approx(89.3718, abs=1e-3)
    red = xplane.reduce_planes(planes, 1.0)
    assert any("pio." in name for name, _ in red["idle_gaps"])
    assert any("jit_fn" in m for m in red["modules"])
    assert any("pio.score_topk" in op for op in red["op_seconds"])


def test_a_cpu_rehearsal_reports_all_seven(capsys, monkeypatch):
    from predictionio_tpu.serving.fastpath import BucketedScorer

    sound = BucketedScorer._device_topk

    def slower(self, users, k):  # so that rows queue and the cut carries
        time.sleep(0.02)
        return sound(self, users, k)

    monkeypatch.setattr(BucketedScorer, "_device_topk", slower)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "wgde-d128.serve-steady", "--seconds", "3",
        "--trace", "1", "--rate", "120", "--rehearse-cpu", "--shrink", "500",
        "--seed", str(2**31 + 25)])
    assert bench_run.main() == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(NEW) <= set(res["metrics"]), sorted(res["metrics"])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 1.0 <= m["batch.passes_per_request"] <= 4.0
    assert m["batch.turnaround_ms"] > 0 and m["batch.run_max_ms"] >= 20.0
    assert 0.0 < m["batch.carried_share"] < 100.0
    assert 0.0 <= m["idle.named_share"] <= 100.0
    assert any(name.startswith("pio.") for name, _ in
               res["breakdown"]["device_ops"])  # the host plane, on a CPU
