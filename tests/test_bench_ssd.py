"""The benchmark's pieces for the parallel state-space / attention family
that can be held on the CPU: its own reference against the repository's and
its controls, its cost function on a case worked by hand, the four new
per-layer readers on a recorded context, the configuration file against the
public catalog's keys, and the cell as ISSUE 41 declares it."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from pio_bench import costs, costs_ssd, costs_wmoe, peaks, reference_ssd  # noqa: E402
from pio_bench.engines import gdn_hybrid_sequence as fixed  # noqa: E402
from pio_bench.engines import ssm_parallel_sequence as family  # noqa: E402
from pio_bench.readers import load_reader  # noqa: E402

from predictionio_tpu.models import ssm_parallel as sp  # noqa: E402
from predictionio_tpu.models.ssm_parallel_reference import reference_forward  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs", "falcon-h1-34b-l6.json")
CELL = "falcon-h1-l6.serve-steady"
NEW = ("ssd.device_share", "ssd.roofline", "ssd.chunk_fill", "hattn.roofline")
CONTROLS = ("drop_ssm", "drop_attention", "wrong_group", "no_conv_bias",
            "no_key_multiplier", "no_rope")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def small(cfg):
    """The rehearsal's widths, f32 weights, one history and both
    references' answers for it."""
    hf = family.model_config(cfg)  # the rehearsal's widths off the chip
    hf["vocab_size"] = 300
    mcfg = sp.SSMParallelConfig.from_hf(hf, max_len=64)
    P = {k: v.astype(jnp.float32) for k, v in sp.init_params(mcfg, 7).items()}
    hist = np.random.default_rng(0).integers(0, 300, 37).astype(np.int32)
    ours = reference_forward(mcfg, P, hist)
    return hf, P, hist, ours, reference_ssd.forward(hf, P, hist)


def test_the_two_references_agree(small):
    """Written apart (one imports nothing from the program), they compute
    the same model: f32 rounding only."""
    hf, P, hist, ours, theirs = small
    np.testing.assert_allclose(theirs["h_last"], ours["h_last"], rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(theirs["x_last"], ours["x_last"], rtol=2e-5,
                               atol=2e-5)
    x0 = hf["embedding_multiplier"] * np.asarray(P["embed"])[hist[-1]]
    np.testing.assert_allclose(theirs["added"], theirs["x_last"] - x0,
                               atol=1e-5)
    rows = [{"history": hist, "h_last": np.asarray(ours["h_last"]),
             "x_last": np.asarray(ours["x_last"])}]
    sound = reference_ssd.compare_trunk(hf, P, rows)
    assert sound["added_rel_err"] < 1e-5 and sound["h_last_rel_err"] < 1e-5
    assert sound["worst_row_tokens"] == 37
    assert reference_ssd.bucket_for(37) == 128
    assert reference_ssd.bucket_for(8192) == 8192


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_is_another_model(small, control):
    """Each mechanism computed wrongly on purpose moves what the layers
    added by a tenth or more (the sound program's bf16 rounding reads under
    a hundredth: tests/test_ssm_parallel.py)."""
    hf, P, hist, ours, _ = small
    rows = [{"history": hist, "h_last": np.asarray(ours["h_last"]),
             "x_last": np.asarray(ours["x_last"])}]
    wrong = reference_ssd.compare_trunk(hf, P, rows, controls=(control,))
    assert wrong["added_rel_err"] > 0.05, (control, wrong)


def test_history_lengths_follow_the_cells_law(cfg):
    spec = cfg["history"]
    assert (spec["median"], spec["sigma"], spec["min"], spec["max"]) == (
        448, 1.1, 32, 8192)
    lengths = fixed.fixed_lengths(cfg["users"], spec)
    assert lengths.min() == 32 and lengths.max() == 8192
    assert 440 <= np.median(lengths) <= 456 and 790 < lengths.mean() < 820
    share = lambda n: float((lengths <= n).mean())
    # ISSUE 41's shares: 30 % fit the 256 rung, 55 % the 512, 77 % the 1,024
    assert abs(share(256) - 0.30) < 0.01 and abs(share(512) - 0.55) < 0.01
    assert abs(share(1024) - 0.77) < 0.01
    assert abs(1 - share(4096) - 0.022) < 0.003
    tokens = lambda n: float(lengths[lengths > n].sum() / lengths.sum())
    assert abs(tokens(1024) - 0.64) < 0.02 and abs(tokens(4096) - 0.18) < 0.02
    a = fixed.make_histories(2 ** 31 + 5, 2048, 500, spec)
    b = fixed.make_histories(2 ** 31 + 6, 2048, 500, spec)
    np.testing.assert_array_equal(a.indptr, b.indptr)  # not the seed's
    assert (a.items[:1000] != b.items[:1000]).any()  # the ids are


def test_the_token_ladder_is_the_programs_default(cfg):
    from predictionio_tpu.serving import seqpath

    assert tuple(cfg["serving"]["token_ladder"]) == seqpath.TOKEN_LADDER
    assert cfg["serving"]["max_rows"] == seqpath.MAX_ROWS
    assert cfg["max_k"] == seqpath.MAX_K
    assert cfg["serving"]["max_len"] == cfg["serving"]["token_ladder"][-1]
    small = cfg["rehearsal"]["serving"]["token_ladder"]
    assert all(b == 2 * a for a, b in zip(small, small[1:]))


def test_cost_function_on_a_case_worked_by_hand():
    c = costs_ssd.state_space_scan(tokens=10, rows=2, heads=6, groups=2,
                                   d_head=4, d_state=8)
    assert c["flops"] == 5 * 10 * 6 * 4 * 8
    assert c["bytes"] == 10 * (6 * 2 * 4 * 2 + 2 * 2 * 8 * 2 + 6 * 4)
    assert c["states"] == 12
    # at the published widths the recurrence is compute-bound: 26.6 ns a
    # token a layer against 22.7 of bytes
    c = costs_ssd.state_space_scan(1, 1, 32, 2, 128, 256)
    assert c["flops"] / 197e12 > c["bytes"] / 819e9


def _ctx(cfg, ops, counters):
    """A traced slice as the harness hands it to a reader: 10 dispatches of
    `pio_seq_forward` taking 0.5 s of device time, the named ops given."""
    import pio_bench.xplane_named as xn

    xn._memo.clear()
    xn._memo["recorded"] = {"ops": ops, "modules": []}
    before = {"fastpath." + k: 0 for k in counters}
    after = {"fastpath." + k: v for k, v in counters.items()}
    return {"cfg": cfg, "counters_before": before, "counters_after": after,
            "device_trace": {"trace_dir": "recorded", "modules": {
                "jit_pio_seq_forward(1)": {"seconds": 0.5, "count": 10}}},
            "peaks": peaks.PEAKS["TPU v5 lite"], "costs": costs,
            "records": [], "traces": []}


def test_the_new_readers_on_a_recorded_context(cfg, monkeypatch):
    ops = ([(f"%pio.ssd_scan.{i}", 0.002) for i in range(6)]
           + [(f"%pio.global_attention.{i}", 0.005) for i in range(6)]
           + [("%fusion.7", 0.2), ("%pio.score_topk.1", 0.03)])
    ctx = _ctx(cfg, ops, {
        "calls": 100, "tokens": 80_000, "scan_tokens": 6 * 80_000,
        "scan_rows": 6 * 110, "scan_chunks": 6 * 100 * 7, "scan_chunk": 128})
    assert load_reader("ssd.device_share")(ctx) == pytest.approx(2.4)
    assert load_reader("ssd.chunk_fill")(ctx) == pytest.approx(
        100 * 80_000 / (100 * 7 * 128))
    # the scan's and the attention's work are the slice's OWN dispatches'
    # (wattn.slice_work): request 0 rode dispatch 3, which the slice holds
    import pio_bench.wattn as wattn

    monkeypatch.setattr(wattn.hostjoin, "analyse", lambda d: {
        "dispatches": [{"seq": 3}, {"seq": 4}]})
    assert load_reader("ssd.roofline")(ctx) is None  # no request joined yet
    ctx["records"] = [{"i": 0, "user": 5}, {"i": 1, "user": 9}]
    ctx["traces"] = [
        {"requestId": "bench-0", "status": 200, "meta": {"dispatch_seq": 3}},
        {"requestId": "bench-1", "status": 200, "meta": {"dispatch_seq": 9}}]
    n = int(wattn.history_lengths(cfg)[5])
    assert load_reader("ssd.roofline")(ctx) == pytest.approx(
        100 * (6 * n * 32 * 5 * 128 * 256 / 197e12) / 0.012)
    cost = costs_wmoe.windowed_attention(
        6 * n * (n + 1) // 2, n, 6, 20, 4, 128)
    assert load_reader("hattn.roofline")(ctx) == pytest.approx(
        100 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9) / 0.03)
    for name in NEW:  # a share of a roofline or of the program: under 100
        assert 0 < load_reader(name)(ctx) < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ops_and_counters_reads_nothing(cfg, name):
    """The parent commit, or another packed family: no such op in the
    trace, no such counter in `GET /`."""
    ctx = _ctx(cfg, [("%pio.mla_attention.3", 0.1), ("%fusion.7", 0.2)],
               {"calls": 100, "tokens": 40_000})
    assert load_reader(name)(ctx) is None
    assert load_reader(name)({**ctx, "device_trace": {"modules": {}}}) is None


def test_configuration_holds_every_published_key_but_the_reduced(cfg, bench):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the public catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 72}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "weights", "event_store", "model_blob"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b-l6.json"
    # the fifth configuration; what later PRs add follows it
    assert 0 < len(entry["why"]) <= 200 and bench["configs"][4] is entry
    # every multiplier to the last digit, and what the family reads of them
    mcfg = sp.SSMParallelConfig.from_hf(
        {**{k: cfg[k] for k in family.MODEL_KEYS}, "vocab_size": cfg["items"]})
    for key in ("attention_in_multiplier", "attention_out_multiplier",
                "embedding_multiplier", "key_multiplier",
                "lm_head_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier"):
        assert getattr(mcfg, key) == row["config"][key]
    assert list(mcfg.ssm_multipliers) == row["config"]["ssm_multipliers"]
    assert list(mcfg.mlp_multipliers) == row["config"]["mlp_multipliers"]
    text = json.dumps(cfg)
    assert "TBD" not in text and "PLACEHOLDER" not in text
    for key in ("block_order", "multipliers", "rope", "gated_norm",
                "dt_softplus", "initial_values", "users", "items", "history",
                "max_len", "max_k", "precision", "token_ladder"):
        assert key in cfg["assumed"], key


def test_the_cut_fills_the_chip_as_the_file_says(cfg):
    mcfg = sp.SSMParallelConfig.from_hf(
        {**{k: cfg[k] for k in family.MODEL_KEYS}, "vocab_size": cfg["items"]},
        max_len=cfg["serving"]["max_len"])
    assert round(mcfg.param_count() * 2 / 1e9, 2) == 10.51
    assert round(mcfg.layer_param_count() * 2 / 1e9, 3) == 0.860
    assert "10.51 GB" in cfg["reduced_why"]["num_hidden_layers"]
    assert "12 " in cfg["deployment"] and "stages of 6" in cfg["deployment"]


def test_the_cell_is_declared_as_the_issue_says(cfg, bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-l6", "serve-steady", 1)
    # the fifth cell of each list; what later PRs add follows it
    assert 0 < len(cell["why"]) <= 200 and bench["workloads"][4] is cell
    assert f"{0.3 * cfg['knee_rps']:g} req/s" in cell["why"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve.p50_ms"]["workloads"][4] == CELL
    assert CELL not in e2e["serve.p95_ms"]["workloads"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= mine
    # every per-layer metric that lists all three older sequence cells
    # is this cell's too, appended directly behind them
    three = ["joyai-flash-l5.serve-steady", "olmo-hybrid-l16.serve-steady",
             "trinity-large-l5.serve-steady"]
    for m in bench["per_layer"]:
        listed = m.get("workloads", ())
        if set(three) <= set(listed):
            at = listed.index(three[-1])
            assert listed[at - 2:at + 2] == three + [CELL], m["name"]
    assert {"seq.device_ms", "head.device_ms", "seq.pad_share",
            "serve.tail_p95_ms.seq", "loadgen.late_ms.seq", "idle.held_share",
            "dispatch.device_ms", "batch.ahead_share", "front.unseen_ms",
            "admit.peak_inflight"} <= mine
    assert not {m for m in mine if m.startswith(
        ("gdn.", "mla.", "moe.", "score.", "attn.", "wattn.", "gattn."))}
    names = [m["name"] for m in bench["per_layer"]]
    # new entries went to the end of their list, behind PR 40's last
    at = names.index(NEW[0])
    assert tuple(names[at:at + 4]) == NEW
    assert names[at - 1] == "batch.ahead_share"
    for m in bench["per_layer"][at:at + 4]:
        # this cell first; a later state-space family that reports the
        # metric too (PR 46) is appended behind it
        assert m["workloads"][0] == CELL and m["moves"] == "serve.p50_ms"
        assert m["unit"] == "%" and m["better"] == "higher"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["hattn.roofline"] == layers["gattn.roofline"]
    assert len({layers[n] for n in NEW[:3]}) == 1


def test_the_gate_is_sized_to_the_cells_rate(cfg):
    """docs/operations.md's rule, p99 x qps + stall seconds x qps, at the
    cell's rate with the longest stall on record (15 s: PERF.md section 7
    Q1)."""
    rate = cfg["knee_rps"] * 0.3  # traffic/serve-steady.json
    assert cfg["serving"]["max_inflight"] >= 1.0 * rate + 15 * rate
    assert "TBD" not in cfg["knee_why"] + cfg["serving"]["max_inflight_why"]
