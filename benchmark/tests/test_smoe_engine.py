"""The state-space / attention family with routed experts behind every
layer: its engine through the whole command at the rehearsal's widths
(``run.py --rehearse-cpu --shrink``).  A sound run is correct and reports
the cell's metrics; a router that weighs its picks by a softmax over ALL the
logits, or a layer that drops its shared expert, is not."""

import json
import sys

import pytest

import run as bench_run

ARGV = ["run.py", "--workload", "granite-h-small-l10.serve-steady",
        "--seconds", "3", "--rate", "8", "--rehearse-cpu", "--shrink", "64"]


def drive(capsys, monkeypatch, seed, trace):
    monkeypatch.setattr(sys, "argv",
                        ARGV + ["--seed", str(seed), "--trace", str(trace)])
    assert bench_run.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_traced_run_is_correct_and_reports_the_cells_metrics(
        capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 46, 1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 24
    assert res["device"]["platform"] == "cpu"  # never a cell's result
    m = res["metrics"]
    # counters exist on the CPU; the device-trace readers find no TPU op
    # names in a host plane and leave their metrics out
    assert 0 < m["ssd.chunk_fill"]["value"] <= 100
    assert 40 < m["moe.local_share"]["value"] < 60  # 12 of 24 held
    assert 0 < m["moe.tile_fill"]["value"] <= 100
    assert m["moe.rows_per_expert"]["value"] > 1
    assert m["moe.load_max_over_mean"]["value"] >= 1
    assert {"seq.pad_share", "seq.tokens_per_dispatch",
            "fastpath.dispatch_ms", "fastpath.d2h_ms", "front.self_ms",
            "batch.passes_per_request", "batch.ahead_share",
            "serve.tail_p95_ms.seq", "admit.peak_inflight"} <= set(m)
    assert not {k for k in m if k.startswith(
        ("gdn.", "mla.", "score.", "wattn.", "gattn.", "smoe."))}


def test_the_end_to_end_metrics_are_p50_and_set_up(capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 47, 0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve.p50_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["softmax_over_all_the_logits",
                                   "shared_expert_dropped"])
def test_a_faulty_feed_forward_is_not_correct(capsys, monkeypatch, fault):
    """Answers are well formed and the head agrees with its own h_last; the
    trunk's comparison with the plain reference says no."""
    import jax

    from predictionio_tpu.models import ssm_moe
    from predictionio_tpu.ops import moe

    if fault == "softmax_over_all_the_logits":
        sound = moe.route_topk_softmax

        def over_all(x, w_gate, *, top_k):
            picked, _, logits = sound(x, w_gate, top_k=top_k)
            full = jax.nn.softmax(logits, axis=1)
            return picked, jax.numpy.take_along_axis(full, picked, 1), logits

        monkeypatch.setattr(moe, "route_topk_softmax", over_all)
    else:
        monkeypatch.setattr(
            ssm_moe, "_swiglu", lambda x, w1, w3, w2: jax.numpy.zeros(
                (x.shape[0], w2.shape[1]), jax.numpy.float32))
    res = drive(capsys, monkeypatch, 2 ** 31 + 48, 0)
    assert res["correct"] is False and res["failed"] == 0
