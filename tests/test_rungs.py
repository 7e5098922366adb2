"""The one dispatch protocol (`serving/rungs.py`, ISSUE 45): compile, warm,
launch, queued readback and the one wait, on a fake compiled callable; and
through both real scorers, that a dispatch's record and `stats()` are the
parent's."""

import time
import types

import numpy as np
import pytest

from predictionio_tpu.obs import tracing
from predictionio_tpu.serving.rungs import RungPrograms


class _Out:
    """A program output that logs what is asked of it."""

    def __init__(self, name, log):
        self.name, self._log = name, log

    def copy_to_host_async(self):
        self._log.append(("copy", self.name))

    def block_until_ready(self):
        self._log.append(("wait", self.name))
        return self

    def __array__(self, *a, **kw):
        self._log.append(("get", self.name))
        return np.zeros(1, np.float32)


LADDER = (8, 16, 64)
DEVICE = types.SimpleNamespace(memory_stats=lambda: None)


def _programs(log, fetch=lambda outs: outs, on_call=None):
    def lower(rung):
        def fn(x):
            log.append(("run", rung, x))
            if on_call is not None:
                on_call(rung)
            return {n: _Out(n, log) for n in ("values", "indices", "h_last")}

        def compile():
            log.append(("compile", rung))
            return fn

        return types.SimpleNamespace(compile=compile)

    return RungPrograms(DEVICE, LADDER, lower,
                        warm_args=lambda r: (f"warm{r}",), fetch=fetch)


def test_construction_compiles_warms_and_measures_in_that_order():
    log = []
    rp = _programs(log)
    steps = [e[:2] for e in log if e[0] in ("compile", "run")]
    assert steps == (
        [("compile", r) for r in LADDER] + [("run", r) for r in LADDER]
        # the lag: the lowest rung twice in a row, five repetitions
        + [("run", LADDER[0])] * 10)
    # every run on the input in the form a dispatch hands over
    assert {e[2] for e in log if e[0] == "run"} == {
        f"warm{r}" for r in LADDER}
    assert rp.compile_count == rp.warmup_executions == len(LADDER)
    assert set(rp.fns) == set(LADDER) and rp.launch_lag_s >= 0.0
    # neither the warm-up nor the measurement is a dispatch
    s = rp.stats()
    assert s["calls"] == s["readbacks_queued"] == s["held_launches"] == 0
    assert s["bucket_hits"] == {str(r): 0 for r in LADDER}


def test_the_copy_is_requested_before_the_wait_once_per_fetched_array(
        monkeypatch):
    from predictionio_tpu.serving import rungs

    log, real = [], rungs.jax.device_get
    rp = _programs(log, fetch=lambda o: {n: o[n] for n in ("values",
                                                           "indices")})
    monkeypatch.setattr(
        rungs.jax, "device_get",
        lambda x: log.append(("device_get",)) or real(x))
    del log[:]
    got, t0, t1 = rp.run(16, lambda: ("x",))
    assert set(got) == {"values", "indices"} and t0 <= t1
    wait = log.index(("device_get",))
    assert log[0] == ("run", 16, "x")
    # asked for between the launch's return and the ONE wait, once each
    assert sorted(log[1:wait]) == [("copy", "indices"), ("copy", "values")]
    assert log.count(("device_get",)) == 1
    assert {e for e in log[wait:] if e[0] == "get"} == {
        ("get", "indices"), ("get", "values")}
    # h_last stays on the device: neither copied nor fetched
    assert not [e for e in log if e[-1] == "h_last"]
    rp.run(64, lambda: ("y",))
    s = rp.stats()
    assert s["calls"] == s["readbacks_queued"] == 2
    assert s["bucket_hits"] == {"8": 0, "16": 1, "64": 1}
    # the audits' call fetches everything and counts nothing
    assert set(rp.direct(8, ("z",))) == {"values", "indices", "h_last"}
    assert rp.stats()["calls"] == rp.stats()["readbacks_queued"] == 2


@pytest.mark.parametrize("more", [False, True])
def test_the_record_is_told_before_the_launch(more):
    seen, woken = [], []
    rec = tracing.Dispatch(3, False, 2, 0, t_run=time.perf_counter(),
                           collect_s=0.0, slow_after_s=2.0)
    rec.on_launch = lambda: woken.append(rec.t_enqueued)
    rp = _programs([], on_call=lambda rung: seen.append(
        (rec.rung, rec.lag, rec.more, rec.t_launch)))
    del seen[:]  # the warm-up's
    staged = []

    def staged_args():
        staged.append(rec.rung)  # the scorer's host stages know the rung
        return ("x",)

    with tracing.scope((), dispatch=rec):
        rp.run(16, staged_args, more=more)
    ((rung, lag, said_more, t_launch),) = seen
    assert staged == [16]
    assert (rung, lag, said_more) == (16, rp.launch_lag_s, more)
    assert rec.stages["device_compute"] > 0 and rec.dc_start is not None
    if more:
        # further launches of the run follow: no estimate of its end can
        # be made from this one, nobody is woken
        assert t_launch is None and rec.t_enqueued is None and not woken
    else:
        assert rec.dc_start <= t_launch <= rec.t_enqueued <= rec.dc_end
        assert woken == [rec.t_enqueued]


# -- through the real scorers ---------------------------------------------------

# `stats()` keys at 511bf64 less the hot-set's block, with PR 48's `compile_s` and
# PR 49's `programs_loaded` (both scorers) and `branch_traces` / `branch_calls` (`latent_moe`'s own); nested under
# `kernel` where the bucketed scorer nests them
BUCKETED_KEYS = {
    "buckets", "top_k", "serving_backend", "sharding", "pod",
    "retrieval_backend", "retrieval", "kernel", "compile_count", "compile_s",
    "programs_loaded", "bucket_hits", "calls", "readbacks_queued", "held_launches",
    "launch_lag_ms", "queries", "padded_rows", "merge_passes",
    "merge_blocks", "row_occupancy", "devprof",
    "kernel.backend", "kernel.factor_dtype", "kernel.resident_factor_bytes",
    "kernel.block_items", "kernel.warmup_executions",
    "kernel.intensity_flops_per_byte",
}
PACKED_KEYS = {
    "family", "token_ladder", "max_rows", "top_k", "backend", "block_items",
    "resident_bytes", "compile_count", "compile_s", "programs_loaded",
    "warmup_executions",
    "bucket_hits", "calls", "readbacks_queued", "held_launches",
    "launch_lag_ms", "queries", "tokens", "padded_tokens", "causal_pairs",
    "merge_passes",
    # latent_moe's own
    "experts", "sparse_layers", "sparse_layer_dispatches", "experts_touched",
    "expert_assignments", "load_max_over_mean_sum", "branch_traces",
    "branch_calls",
}


def _bucketed():
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving.fastpath import BucketedScorer

    rng = np.random.default_rng(5)
    sc = BucketedScorer(
        MeshContext.create(), rng.normal(size=(40, 6)).astype(np.float32),
        rng.normal(size=(29, 6)).astype(np.float32), max_k=5)
    return sc, sc.buckets, np.arange(70, dtype=np.int32) % 40, BUCKETED_KEYS


def _packed():
    from predictionio_tpu.models import latent_moe as model
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    cfg = model.LatentMoEConfig.from_hf(dict(
        vocab_size=300, hidden_size=64, num_hidden_layers=2,
        intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=8, num_experts_per_tok=2, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16), max_len=64)
    sc = PackedSequenceScorer(cfg, model.init_params(cfg, 45), max_k=5,
                              ladder=(64, 128), max_rows=4)
    rng = np.random.default_rng(45)
    rows = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in (5, 20, 17, 3, 60, 64, 20)]  # 3 dispatches
    return sc, sc.ladder, rows, PACKED_KEYS


def _keys(d, nest=("kernel",), pre=""):
    out = set()
    for k, v in d.items():
        out.add(pre + k)
        if k in nest:
            out |= _keys(v, (), k + ".")
    return out


@pytest.mark.parametrize("build", [_bucketed, _packed])
def test_a_dispatch_through_either_scorer_is_the_one_protocol(
        build, monkeypatch):
    sc, ladder, over_top, golden = build()
    s = sc.stats()
    assert _keys(s) == golden
    assert s["compile_count"] == sc.compile_count == len(ladder)
    # the ladder's compiles timed as one wall, no program more for it
    assert 0.0 < s["compile_s"] == round(sc._rungs.compile_s, 4)
    assert s.get("warmup_executions",
                 s.get("kernel", {}).get("warmup_executions")) == len(ladder)
    assert s["calls"] == s["readbacks_queued"] == 0

    seen, spans, real = [], [], tracing.annotation
    monkeypatch.setattr(
        tracing, "annotation",
        lambda name, **kv: seen.append(name) or real(name, **kv))

    class Rec(tracing.Dispatch):
        def add_stage(self, name, t0, t1):
            spans.append((name, t0, t1))
            super().add_stage(name, t0, t1)

    rec = Rec(9, False, 1, 0, t_run=time.perf_counter(), collect_s=0.0,
              slow_after_s=2.0)
    with tracing.scope((), dispatch=rec):
        idx, val = sc.score_topk(over_top, 3)
    assert idx.shape == val.shape == (len(over_top), 3)
    s = sc.stats()
    assert s["calls"] == s["readbacks_queued"] == sum(
        s["bucket_hits"].values()) >= 2
    assert s["compile_count"] == len(ladder)  # no request compiles
    assert s["compile_s"] == round(sc._rungs.compile_s, 4)  # nor is timed
    # every dispatch: h2d, device_compute, d2h one after the other, the
    # launch inside device_compute
    stages = [n for n in seen if n != "pio.batch_assembly"]
    assert stages == ["pio.h2d", "pio.device_compute", "pio.launch",
                      "pio.d2h"] * s["calls"]
    three = [sp for sp in spans if sp[0] != "batch_assembly"]
    for h2d, dc, d2h in zip(three[0::3], three[1::3], three[2::3]):
        assert (h2d[0], dc[0], d2h[0]) == ("h2d", "device_compute", "d2h")
        assert h2d[1] <= h2d[2] <= dc[1] < dc[2] <= d2h[1] <= d2h[2]
    assert (rec.dc_start, rec.dc_end) == (three[1][1], three[-2][2])
    # told of the LAST launch only: the ones before it said `more`
    assert rec.rung in ladder and rec.lag == sc._rungs.launch_lag_s
    assert rec.more is False and rec.t_enqueued is not None
    assert three[-2][1] <= rec.t_launch <= rec.t_enqueued <= three[-2][2]
