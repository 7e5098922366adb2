"""Drive the rest of a run past the harness's look for a chip (a CPU
rehearsal at 1/500 of the catalog) and see what ``correct`` says when the
timed path is sound, when it is broken underneath, and when requests are
shed."""

import json
import sys

import numpy as np
import run as bench_run

ARGV = ["run.py", "--workload", "wgde-d128.serve-steady", "--seconds", "2",
        "--trace", "0", "--rate", "60", "--rehearse-cpu", "--shrink", "500"]


def drive(capsys, monkeypatch, seed):
    monkeypatch.setattr(sys, "argv", ARGV + ["--seed", str(seed)])
    assert bench_run.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_run_is_correct(capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2**31 + 11)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 120
    assert set(res["metrics"]) == {"serve.p50_ms", "serve.p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu"  # never a cell's result


def test_a_score_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from predictionio_tpu.serving.fastpath import BucketedScorer

    sound = BucketedScorer._device_topk

    def one_bf16_pass(self, users, k):
        import ml_dtypes

        idx, vals = sound(self, users, k)
        return idx, np.asarray(vals).astype(ml_dtypes.bfloat16).astype(
            np.float32)

    monkeypatch.setattr(BucketedScorer, "_device_topk", one_bf16_pass)
    res = drive(capsys, monkeypatch, 2**31 + 12)
    assert res["correct"] is False and res["failed"] == 0


def test_a_best_item_left_out_is_not_correct(capsys, monkeypatch):
    from predictionio_tpu.serving.fastpath import BucketedScorer

    sound = BucketedScorer._device_topk

    def drop_the_best(self, users, k):
        idx, vals = sound(self, users, min(k + 1, self.k))
        return np.asarray(idx)[:, 1:], np.asarray(vals)[:, 1:]

    monkeypatch.setattr(BucketedScorer, "_device_topk", drop_the_best)
    res = drive(capsys, monkeypatch, 2**31 + 13)
    assert res["correct"] is False


def test_shed_requests_are_failed_not_incorrect(capsys, monkeypatch):
    """Bursts of 48 against an admission bound of 2: most of a burst gets a
    503.  They are `failed`; the answers that did arrive are still right."""
    from predictionio_tpu.serving.query_server import QueryServer

    real_init = QueryServer.__init__

    def tight_admission(self, *a, **kw):
        kw["max_inflight"] = 2
        real_init(self, *a, **kw)

    monkeypatch.setattr(QueryServer, "__init__", tight_admission)
    # the burst mix is not a cell (PERF.md section 7 Q1): lend it one here
    real_load = bench_run.load_json

    def with_burst_cell(*parts):
        loaded = real_load(*parts)
        if parts[-1] == "BENCHMARK.json":
            loaded["workloads"].append({
                "name": "wgde-d128.serve-burst", "config": "als-wgde-d128",
                "traffic": "serve-burst", "chips": 1, "why": "a test's"})
        return loaded

    monkeypatch.setattr(bench_run, "load_json", with_burst_cell)
    argv = [x if x != "wgde-d128.serve-steady" else "wgde-d128.serve-burst"
            for x in ARGV]
    monkeypatch.setattr(sys, "argv", argv + ["--seed", str(2**31 + 14)])
    assert bench_run.main() == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["failed"] > 0 and res["correct"] is True
    assert res["attempted"] == 120


def test_a_stalled_dispatch_leaves_its_stack_in_the_dump(
        capsys, monkeypatch, tmp_path):
    """`PIO_BENCH_STALL_DUMP`: one dispatch that holds the batcher for 2.5 s
    is caught with the frame it sits in; nothing is shed, the run is
    correct, and the earlier lines name the silence."""
    import time

    from predictionio_tpu.serving.fastpath import BucketedScorer

    sound = BucketedScorer._device_topk
    calls = {"n": 0}

    def stalls_once(self, users, k):
        calls["n"] += 1
        if calls["n"] == 20:  # warm-up makes five calls; this is mid-window
            time.sleep(2.5)
        return sound(self, users, k)

    monkeypatch.setattr(BucketedScorer, "_device_topk", stalls_once)
    dump = tmp_path / "stall.txt"
    monkeypatch.setenv("PIO_BENCH_STALL_DUMP", str(dump))
    monkeypatch.setattr(sys, "argv", ARGV + ["--seed", str(2**31 + 15)])
    assert bench_run.main() == 0
    out = capsys.readouterr().out
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert "stalls_once" in dump.read_text()
    silence = float(out.split("longest silence between answers ")[1].split()[0])
    assert silence > 2.0
