"""The parallel state-space / attention family's engine through the whole
command at the rehearsal's widths (``run.py --rehearse-cpu --shrink``): a
sound run is correct and reports the cell's metrics; a scan that forgets its
state underneath, or a block that drops its attention, is not."""

import json
import sys

import pytest

import run as bench_run

ARGV = ["run.py", "--workload", "falcon-h1-l6.serve-steady", "--seconds",
        "3", "--rate", "8", "--rehearse-cpu", "--shrink", "64"]


def drive(capsys, monkeypatch, seed, trace):
    monkeypatch.setattr(sys, "argv",
                        ARGV + ["--seed", str(seed), "--trace", str(trace)])
    assert bench_run.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_traced_run_is_correct_and_reports_the_cells_metrics(
        capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 41, 1)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 24
    assert res["device"]["platform"] == "cpu"  # never a cell's result
    m = res["metrics"]
    # counters exist on the CPU; the device-trace readers find no TPU op
    # names in a host plane and leave their metrics out
    assert 0 < m["ssd.chunk_fill"]["value"] <= 100
    assert {"seq.pad_share", "seq.tokens_per_dispatch",
            "fastpath.dispatch_ms", "fastpath.d2h_ms", "front.self_ms",
            "batch.passes_per_request", "batch.ahead_share",
            "serve.tail_p95_ms.seq", "admit.peak_inflight"} <= set(m)
    assert not {k for k in m if k.startswith(
        ("gdn.", "mla.", "moe.", "score.", "wattn.", "gattn."))}


def test_the_end_to_end_metrics_are_p50_and_set_up(capsys, monkeypatch):
    res = drive(capsys, monkeypatch, 2 ** 31 + 42, 0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve.p50_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["scan_forgets_its_state",
                                   "attention_dropped"])
def test_a_faulty_mixer_is_not_correct(capsys, monkeypatch, fault):
    """Answers are well formed and the head agrees with its own h_last; the
    trunk's comparison with the plain reference says no."""
    from predictionio_tpu.models import ssm_parallel
    from predictionio_tpu.ops import ssd_scan

    if fault == "scan_forgets_its_state":
        sound = ssd_scan.ssd_scan

        def forgetful(x, b, c, dt, a, d, seg_start, **kw):
            # every token a history of its own: no state is carried
            import jax.numpy as jnp
            return sound(x, b, c, dt, a, d,
                         jnp.arange(x.shape[0], dtype=jnp.int32), **kw)

        monkeypatch.setattr(ssd_scan, "ssd_scan", forgetful)
    else:
        monkeypatch.setattr(
            ssm_parallel, "attention_branch",
            lambda cfg, W, a, *rest: 0.0 * a)
    res = drive(capsys, monkeypatch, 2 ** 31 + 43, 0)
    assert res["correct"] is False and res["failed"] == 0
