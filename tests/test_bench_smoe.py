"""The benchmark's pieces for the state-space / attention family with routed
experts behind every layer that can be held on the CPU: its own reference
against the repository's and its controls, the four new per-layer readers on
a recorded context, the configuration file against the public catalog's
keys, and the cell as ISSUE 46 declares it."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from pio_bench import costs, costs_ssd, costs_wmoe, peaks, reference_smoe  # noqa: E402
from pio_bench.engines import gdn_hybrid_sequence as fixed  # noqa: E402
from pio_bench.engines import ssm_moe_sequence as family  # noqa: E402
from pio_bench.readers import load_reader  # noqa: E402

from predictionio_tpu.models import ssm_moe as sm  # noqa: E402
from predictionio_tpu.models import ssm_moe_reference as ref  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "granite-4.0-h-small-l10-ep2.json")
CELL = "granite-h-small-l10.serve-steady"
NEW = ("smoe.scan_roofline", "smoe.attn_roofline", "moe.rows_per_expert",
       "moe.tile_fill")
CONTROLS = ("drop_shared", "softmax_over_all", "no_residual_multiplier",
            "no_embedding_multiplier", "attention_scale_rsqrt",
            "rope_on_attention", "drop_attention", "drop_scan",
            "no_conv_bias", "gated_norm_two_groups", "unheld_as_held")
OLDER = ["joyai-flash-l5.serve-steady", "olmo-hybrid-l16.serve-steady",
         "trinity-large-l5.serve-steady", "falcon-h1-l6.serve-steady"]


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
    """The rehearsal's widths, f32 weights, one history, the repository's
    reference's answer for it and the reference's own picks."""
    hf = family.model_config(cfg)  # the rehearsal's widths off the chip
    hf["vocab_size"] = 304
    mcfg = sm.SSMMoEConfig.from_hf(hf, max_len=64)
    P = {k: v.astype(jnp.float32) for k, v in sm.init_params(mcfg, 7).items()}
    hist = np.random.default_rng(0).integers(0, 300, 37).astype(np.int32)
    ours = ref.reference_forward(mcfg, P, hist)
    # the picks the repository's reference makes, layer by layer
    picks, x = [], mcfg.embedding_multiplier * P["head"][hist]
    for i in range(mcfg.num_hidden_layers):
        kind, W = ref.layer_weights(mcfg, P, i)
        x_mid = x + mcfg.residual_multiplier * (
            ref.mamba_mixer if kind == "mamba" else ref.attention_mixer)(
                mcfg, W, ref._rms(x, W["in_norm"], mcfg.rms_norm_eps))
        picks.append(np.asarray(ref.route(mcfg, W, ref._rms(
            x_mid, W["ffn_norm"], mcfg.rms_norm_eps))[0]))
        x, _ = ref.layer(mcfg, kind, W, x)
    return hf, P, hist, ours, np.stack(picks).astype(np.int32)


def _rows(small):
    _, _, hist, ours, picks = small
    return [{"history": hist, "picks": picks,
             "h_last": np.asarray(ours["h_last"]),
             "x_last": np.asarray(ours["x_last"])}]


def test_the_two_references_agree(small):
    """Written apart (one imports nothing from the program), they compute
    the same model: f32 rounding only; and the benchmark's, forced to the
    repository's picks, finds every one of them admissible."""
    hf, P, hist, ours, picks = small
    theirs = reference_smoe.forward(hf, P, hist, picks)
    np.testing.assert_allclose(theirs["h_last"], ours["h_last"], rtol=5e-5,
                               atol=1e-7)
    np.testing.assert_allclose(theirs["x_last"], ours["x_last"], rtol=5e-5,
                               atol=5e-5)
    x0 = hf["embedding_multiplier"] * np.asarray(P["head"])[hist[-1]]
    np.testing.assert_allclose(theirs["added"], theirs["x_last"] - x0,
                               atol=1e-5)
    assert theirs["violation"] < 1e-5 and theirs["flipped"] == 0
    assert theirs["decisions"] == 37 * hf["num_hidden_layers"]
    sound = reference_smoe.compare_trunk(hf, P, _rows(small))
    assert sound["added_rel_err"] < 1e-5 and sound["h_last_rel_err"] < 1e-5
    assert sound["route_violation"] < 1e-5
    assert sound["worst_row_tokens"] == 37
    assert reference_smoe.bucket_for(37) == 128
    assert reference_smoe.bucket_for(8192) == 8192
    assert reference_smoe.runs_of(hf["layer_types"]) == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    # picks that are NOT the ten largest are a violation, not an error
    worse = picks.copy()
    worse[3, :, 0] = (worse[3, :, 0] + 1) % 24
    assert reference_smoe.forward(hf, P, hist, worse)["flipped"] > 0


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_is_another_model(small, control):
    """Each mechanism computed wrongly on purpose moves what the layers
    added by a twentieth or more (the sound program's bf16 rounding reads
    under a hundredth: tests/test_ssm_moe.py)."""
    hf, P, _, _, _ = small
    wrong = reference_smoe.compare_trunk(hf, P, _rows(small),
                                         controls=(control,))
    assert wrong["added_rel_err"] > 0.05, (control, wrong)


def test_history_lengths_are_the_falcon_cells_law_letter_for_letter(cfg):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        falcon = json.load(f)
    assert cfg["history"] == falcon["history"]
    assert cfg["users"] == falcon["users"] == 131072
    lengths = fixed.fixed_lengths(cfg["users"], cfg["history"])
    share = lambda n: float((lengths <= n).mean())
    assert abs(share(256) - 0.305) < 0.01 and abs(share(512) - 0.548) < 0.01
    assert abs(share(1024) - 0.774) < 0.01
    assert abs(1 - share(4096) - 0.022) < 0.003
    assert 790 < lengths.mean() < 820


def test_the_token_ladder_is_the_programs_default(cfg):
    from predictionio_tpu.serving import seqpath

    assert tuple(cfg["serving"]["token_ladder"]) == seqpath.TOKEN_LADDER
    assert cfg["serving"]["max_rows"] == seqpath.MAX_ROWS
    assert cfg["max_k"] == seqpath.MAX_K
    assert cfg["serving"]["max_len"] == cfg["serving"]["token_ladder"][-1]


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
    ops = ([(f"%pio.ssd_scan.{i}", 0.004) for i in range(9)]
           + [("%pio.global_attention.1", 0.005)]
           + [(f"%pio.moe_experts.{i}", 0.003) for i in range(30)]
           + [("%fusion.7", 0.2), ("%pio.score_topk.1", 0.03)])
    ctx = _ctx(cfg, ops, {
        "calls": 100, "tokens": 80_000, "expert_assignments": 4_000_000,
        "experts_touched": 36_000, "expert_row_tiles": 50_000,
        "routed_assignments": 8_000_000})
    assert load_reader("moe.rows_per_expert")(ctx) == pytest.approx(
        4_000_000 / 36_000)
    assert load_reader("moe.tile_fill")(ctx) == pytest.approx(
        100 * 4_000_000 / (50_000 * 128))
    assert load_reader("moe.local_share")(ctx) == pytest.approx(50.0)
    import pio_bench.wattn as wattn

    monkeypatch.setattr(wattn.hostjoin, "analyse", lambda d: {
        "dispatches": [{"seq": 3}, {"seq": 4}]})
    assert load_reader("smoe.scan_roofline")(ctx) is None  # nobody joined
    ctx["records"] = [{"i": 0, "user": 5}, {"i": 1, "user": 9}]
    ctx["traces"] = [
        {"requestId": "bench-0", "status": 200, "meta": {"dispatch_seq": 3}},
        {"requestId": "bench-1", "status": 200, "meta": {"dispatch_seq": 9}}]
    n = int(wattn.history_lengths(cfg)[5])
    # NINE layers scan, whatever the chunking: 5 x 64 x 128 a token a head
    cost = costs_ssd.state_space_scan(9 * n, 0, 128, 1, 64, 128)
    assert cost["flops"] == 5.0 * 9 * n * 128 * 64 * 128
    assert load_reader("smoe.scan_roofline")(ctx) == pytest.approx(
        100 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9) / 0.036)
    # ONE layer attends: 32 / 8 heads of 4,096 / 32 = 128
    cost = costs_wmoe.windowed_attention(n * (n + 1) // 2, n, 1, 32, 8, 128)
    assert load_reader("smoe.attn_roofline")(ctx) == pytest.approx(
        100 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9) / 0.005)
    for name in ("smoe.scan_roofline", "smoe.attn_roofline", "moe.tile_fill"):
        assert 0 < load_reader(name)(ctx) < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ops_and_counters_reads_nothing(cfg, name):
    """The parent commit, or another packed family (whose configuration has
    no stage's layer kinds either): no such op, no such counter."""
    ctx = _ctx(cfg, [("%pio.mla_attention.3", 0.1), ("%fusion.7", 0.2)],
               {"calls": 100, "tokens": 40_000})
    assert load_reader(name)(ctx) is None
    assert load_reader(name)({**ctx, "device_trace": {"modules": {}}}) is None
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        other = json.load(f)
    ops = [("%pio.ssd_scan.1", 0.1), ("%pio.global_attention.1", 0.1)]
    assert load_reader(name)(_ctx(other, ops, {"calls": 100})) is None


def test_configuration_holds_every_published_key_but_the_reduced(cfg, bench):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the public catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_local_experts"}
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72}
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"]) == (10, 36)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_local_experts", "weights", "event_store",
        "model_blob"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small-l10-ep2.json"
    # the sixth configuration; what later PRs add follows it
    assert 0 < len(entry["why"]) <= 200 and bench["configs"][5] is entry
    hf = {**{k: cfg[k] for k in family.MODEL_KEYS},
          "layer_types": cfg["layer_types"][:10], "vocab_size": cfg["items"],
          "num_local_experts": 72, "num_experts_held": 36}
    mcfg = sm.SSMMoEConfig.from_hf(hf)
    for key in ("attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling"):
        assert getattr(mcfg, key) == row["config"][key]
    assert mcfg.runs == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert cfg["moe_intermediate_size"] == cfg["intermediate_size"] == 768
    text = json.dumps(cfg)
    assert "TBD" not in text and "PLACEHOLDER" not in text
    for key in ("block_order", "multipliers", "rope", "gated_norm",
                "dt_softplus", "router", "initial_values", "users", "items",
                "history", "max_len", "max_k", "precision", "token_ladder",
                "moe_intermediate_size"):
        assert key in cfg["assumed"], key


def test_the_cut_fills_the_chip_as_the_file_says(cfg):
    hf = {**{k: cfg[k] for k in family.MODEL_KEYS},
          "layer_types": cfg["layer_types"][:10], "vocab_size": cfg["items"],
          "num_local_experts": 72, "num_experts_held": 36}
    mcfg = sm.SSMMoEConfig.from_hf(hf, max_len=cfg["serving"]["max_len"])
    assert round(mcfg.param_count() * 2 / 1e9, 2) == 9.93
    assert "9.93 GB" in cfg["reduced_why"]["num_hidden_layers"]
    assert "4 pipeline stages of 10" in cfg["deployment"]
    assert "HALF" in cfg["deployment"]
    assert cfg["stage"] == {**cfg["stage"], "first_layer": 0,
                            "expert_ranks": 2, "expert_rank": 0}


def test_the_cell_is_declared_as_the_issue_says(cfg, bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small-l10-ep2", "serve-steady", 1)
    # the sixth cell of each list; what later PRs add follows it
    assert 0 < len(cell["why"]) <= 200 and bench["workloads"][5] is cell
    assert f"{0.3 * cfg['knee_rps']:g} req/s" in cell["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve.p50_ms"]["workloads"][5] == CELL
    assert CELL not in e2e["serve.p95_ms"]["workloads"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= mine
    # every per-layer metric that lists all four older sequence cells is
    # this cell's too, appended directly behind them
    for m in bench["per_layer"]:
        listed = m.get("workloads", ())
        if set(OLDER) <= set(listed):
            at = listed.index(OLDER[-1])
            assert listed[at - 3:at + 2] == OLDER + [CELL], m["name"]
    assert {"seq.device_ms", "head.device_ms", "seq.pad_share",
            "serve.tail_p95_ms.seq", "loadgen.late_ms.seq", "idle.held_share",
            "dispatch.device_ms", "batch.ahead_share", "front.unseen_ms",
            "admit.peak_inflight", "ssd.device_share", "ssd.chunk_fill",
            "moe.device_share", "moe.roofline", "moe.load_max_over_mean",
            "moe.local_share"} <= mine
    # both multiply by num_hidden_layers: wrong at nine and one
    assert not {"ssd.roofline", "hattn.roofline"} & mine
    assert not {m for m in mine if m.startswith(
        ("gdn.", "mla.", "score.", "attn.", "wattn.", "gattn."))}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])  # new entries went to the end of their list
    assert tuple(names[at:at + 4]) == NEW
    assert names[at - 1] == "seq.dense_tile_share"
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve.p50_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["smoe.scan_roofline"] == layers["ssd.roofline"]
    assert layers["smoe.attn_roofline"] == layers["hattn.roofline"]
    assert layers["moe.tile_fill"] == layers["moe.rows_per_expert"] \
        == layers["moe.roofline"]


def test_the_gate_is_sized_to_the_cells_rate(cfg):
    """docs/operations.md's rule, p99 x qps + stall seconds x qps, at the
    cell's rate with the longest stall on record (15 s: PERF.md section 7
    Q1)."""
    rate = cfg["knee_rps"] * 0.3  # traffic/serve-steady.json
    assert cfg["serving"]["max_inflight"] >= 1.0 * rate + 15 * rate
    assert "TBD" not in cfg["knee_why"] + cfg["serving"]["max_inflight_why"]
