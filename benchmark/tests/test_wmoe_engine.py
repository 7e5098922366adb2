"""The window/global sparse-expert family's engine through the whole
command at the rehearsal's widths (``run.py --rehearse-cpu --shrink``): a
sound run is correct and reports the cell's metrics; an attention that
forgets its window underneath is not."""

import json
import sys

import run as bench_run

ARGV = ["run.py", "--workload", "trinity-large-l5.serve-steady", "--seconds",
        "3", "--rate", "8", "--rehearse-cpu", "--shrink", "64"]


def drive(capsys, monkeypatch, seed, trace):
    monkeypatch.setattr(sys, "argv",
                        ARGV + ["--seed", str(seed), "--trace", str(trace)])
    assert bench_run.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_traced_run_is_correct_and_reports_the_cells_metrics(
        capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 39, 1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 24
    assert res["device"]["platform"] == "cpu"  # never a cell's result
    m = res["metrics"]
    # counters exist on the CPU; the device-trace readers find no TPU op
    # names in a host plane and leave their metrics out
    assert 15 < m["moe.local_share"]["value"] < 35  # 4 of 16 held
    assert 0 < m["wattn.kv_blocks_share"]["value"] <= 100
    assert {"seq.pad_share", "seq.tokens_per_dispatch",
            "moe.load_max_over_mean", "fastpath.dispatch_ms",
            "fastpath.d2h_ms", "front.self_ms", "batch.passes_per_request",
            "serve.tail_p95_ms.seq"} <= set(m)
    assert not {k for k in m if k.startswith(("gdn.", "mla.", "score."))}


def test_the_end_to_end_metrics_are_p50_and_set_up(capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 40, 0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve.p50_ms", "setup_s"}


def test_an_attention_that_forgets_its_window_is_not_correct(
        capsys, monkeypatch):
    """The window layers computed as global ones: answers are well formed,
    the head agrees with its own h_last, and the trunk's comparison with
    the plain reference says no."""
    from predictionio_tpu.ops import flash_attention

    sound = flash_attention.packed_grouped_attention

    def no_window(q, k, v, seg_start, *, window=None, **kw):
        return sound(q, k, v, seg_start, window=None, **kw)

    monkeypatch.setattr(flash_attention, "packed_grouped_attention",
                        no_window)
    res = drive(capsys, monkeypatch, 2 ** 31 + 41, 0)
    assert res["correct"] is False and res["failed"] == 0
