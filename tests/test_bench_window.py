"""The benchmark's pieces for the window/global sparse-expert family that
can be held on the CPU: its own reference against the repository's (and its
three controls), the served answers it re-runs, its cost function on a case
worked by hand, the five new per-layer readers on a recorded context, and
the configuration file against the public catalog's keys."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from pio_bench import costs, costs_wmoe, peaks, reference_wmoe  # noqa: E402
from pio_bench.engines import window_moe_sequence as family  # noqa: E402
from pio_bench.readers import load_reader  # noqa: E402

from predictionio_tpu.models import window_moe as wm  # noqa: E402
from predictionio_tpu.models.window_moe_reference import reference_forward  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "trinity-large-l5-ep8.json")
CELL = "trinity-large-l5.serve-steady"
NEW = ("wattn.device_share", "wattn.roofline", "gattn.roofline",
       "wattn.kv_blocks_share", "moe.local_share")
SHARED_MOE = ("moe.device_share", "moe.roofline", "moe.load_max_over_mean")


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
    """The rehearsal's widths (off the chip), f32 weights, one history
    longer than the rehearsal's window of 16 and the program's row for it."""
    hf = family.model_config(cfg)
    hf["vocab_size"] = 300
    mcfg = wm.WindowMoEConfig.from_hf(hf, max_len=64)
    P = {k: v.astype(jnp.float32) for k, v in wm.init_params(mcfg, 7).items()}
    hist = np.random.default_rng(0).integers(0, 300, 37).astype(np.int32)
    ours = reference_forward(mcfg, P, hist)
    row = {"history": hist, "picks": np.asarray(ours["picks"]),
           "h_last": np.asarray(ours["h_last"]),
           "x_last": np.asarray(ours["x_last"])}
    return hf, mcfg, P, row


def test_the_model_config_is_the_stages_layers_and_the_ranks_experts(cfg):
    hf = family.model_config(cfg)  # the rehearsal's widths off the chip
    assert hf["layer_types"] == cfg["layer_types"][5:10] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert (hf["num_experts"], hf["num_experts_held"],
            hf["first_expert_held"]) == (16, 4, 0)
    assert hf["num_dense_layers"] == 1 and hf["num_experts_per_tok"] == 4
    other = family.model_config({**cfg, "stage": {**cfg["stage"],
                                                   "expert_rank": 3}})
    assert other["first_expert_held"] == 12


def test_the_two_references_agree_and_the_controls_do_not(small):
    """Written apart (one imports nothing from the program), they compute
    the same model: f32 rounding only; each control is another model."""
    hf, mcfg, P, row = small
    theirs = reference_wmoe.forward(hf, P, row["history"], row["picks"])
    np.testing.assert_allclose(theirs["h_last"], row["h_last"], rtol=2e-5,
                               atol=2e-6)
    assert theirs["violation"] < 1e-6 and theirs["flipped"] == 0
    sound = reference_wmoe.compare_trunk(hf, P, [row])
    assert sound["added_rel_err"] < 1e-5 and sound["h_last_rel_err"] < 1e-5
    assert sound["route_violation"] < 1e-6 and sound["rows"] == 1
    for control in ("drop_window", "rope_on_global", "kv_modulo"):
        wrong = reference_wmoe.compare_trunk(hf, P, [row],
                                             controls=(control,))
        assert wrong["added_rel_err"] > 0.1, control
    # a history inside the window cannot tell that the window was dropped
    short = dict(row, history=row["history"][:12])
    ours = reference_forward(mcfg, P, short["history"])
    short.update(picks=np.asarray(ours["picks"]),
                 h_last=np.asarray(ours["h_last"]),
                 x_last=np.asarray(ours["x_last"]))
    assert reference_wmoe.compare_trunk(
        hf, P, [short], controls=("drop_window",))["added_rel_err"] < 1e-5


def test_the_reference_is_given_the_same_held_experts(small):
    """A pick outside the held slice adds nothing: forcing every token onto
    experts held elsewhere leaves the shared expert alone, and the
    comparison then says the program's row is another model's."""
    hf, _, P, row = small
    away = np.full_like(row["picks"], 9)  # experts 4..15 are held elsewhere
    away[..., 1:] = [10, 11, 12]
    moved = reference_wmoe.compare_trunk(hf, P, [dict(row, picks=away)])
    assert moved["added_rel_err"] > 0.05 and moved["route_violation"] > 0
    assert moved["flipped_decisions"] > 0


def test_trunk_problems_names_each_limit_that_is_passed(cfg):
    g = cfg["guarantees"]
    fine = {"added_rel_err": g["trunk_tolerance"] / 2,
            "h_last_rel_err": g["h_last_tolerance"] / 2,
            "route_violation": g["route_tolerance"] / 2}
    assert family.trunk_problems(fine, g) == []
    for name in fine:
        assert family.trunk_problems({**fine, name: 1.0}, g) == [name]


def test_the_served_sample_holds_the_longest_and_the_longest_over_the_window():
    lengths = [40, 5000, 17, 9000, 300, 4097, 12000, 6000, 4096] + [50] * 200
    recs = [({"user": j}, None, None) for j in range(len(lengths))]
    hist_of = lambda rec: np.zeros(lengths[rec["user"]])
    gen = np.random.default_rng(3)
    got = family.sample_served(recs, hist_of, 20, gen, window=4096)
    users = {rec["user"] for rec, _, _ in got}
    assert {6, 3, 7, 1} <= users  # 12,000 the longest; 9,000 6,000 5,000
    assert 20 <= len(got) <= 24
    assert family.sample_served(recs[:5], hist_of, 20, gen, 4096) == recs[:5]


def test_history_lengths_follow_the_configurations_law(cfg):
    from pio_bench.engines.gdn_hybrid_sequence import fixed_lengths

    lengths = fixed_lengths(cfg["users"], cfg["history"])
    assert lengths.min() >= 32 and lengths.max() == 16384
    assert np.median(lengths) == 1024 and 1900 < lengths.mean() < 2100
    long = lengths > cfg["sliding_window"]
    assert 0.11 < long.mean() < 0.14  # 12 % of histories ...
    assert 0.45 < lengths[long].sum() / lengths.sum() < 0.55  # half the tokens
    ladder = cfg["serving"]["token_ladder"]
    assert ladder == [256 * 2 ** i for i in range(7)]
    assert ladder[-1] == cfg["serving"]["max_len"] == cfg["history"]["max"]


def test_cost_function_on_a_case_worked_by_hand():
    c = costs_wmoe.windowed_attention(pairs=10, tokens=4, layers=3,
                                      q_heads=6, kv_heads=2, d_head=8)
    assert c["flops"] == 2 * 6 * 10 * 2 * 8
    # q and o once a query head, k and v once a KV head
    assert c["bytes"] == 3 * 4 * (2 * 6 + 2 * 2) * 8 * 2


def _ctx(cfg, ops, counters, riders=()):
    """A traced slice as the harness hands it to a reader: 10 dispatches of
    `pio_seq_forward` taking 0.5 s of device time, the named ops given;
    ``riders``: (dispatch seq, user) of the window's requests, of which the
    slice's joined spans carry the seqs 7 and 9."""
    import pio_bench.hostjoin as hj
    import pio_bench.xplane_named as xn

    xn._memo.clear()
    xn._memo["recorded"] = {"ops": ops, "modules": []}
    hj._memo.clear()
    hj._memo["recorded"] = {"dispatches": [{"seq": 7}, {"seq": 9}]}
    before = {"fastpath." + k: 0 for k in counters}
    after = {"fastpath." + k: v for k, v in counters.items()}
    return {"cfg": cfg, "counters_before": before, "counters_after": after,
            "records": [{"i": i, "user": u} for i, (_, u) in enumerate(riders)],
            "traces": [{"requestId": f"bench-{i}", "status": 200,
                        "meta": {"dispatch_seq": seq}}
                       for i, (seq, _) in enumerate(riders)],
            "device_trace": {"trace_dir": "recorded", "modules": {
                "jit_pio_seq_forward(1)": {"seconds": 0.5, "count": 10}}},
            "peaks": peaks.PEAKS["TPU v5 lite"], "costs": costs}


def test_the_new_readers_on_a_recorded_context(cfg):
    from pio_bench import wattn

    ops = ([(f"%pio.window_attention.{i}", 0.060) for i in range(4)]
           + [("%pio.global_attention.1", 0.100)]
           + [(f"%pio.moe_experts.{i}", 0.010) for i in range(12)]
           + [("%fusion.7", 0.03), ("%pio.score_topk.1", 0.01)])
    lengths = wattn.history_lengths(cfg)
    long_user = int(np.flatnonzero(lengths == 16384)[0])
    short_user = int(np.flatnonzero(lengths == 700)[0])
    other = int(np.flatnonzero(lengths > 9000)[1])
    # dispatch 7 carried the long and the short history, 9 the long one
    # again; dispatch 8 (another long row) lay outside the slice
    riders = [(7, long_user), (7, short_user), (8, other), (9, long_user)]
    n_tok, calls = 8000, 100
    ctx = _ctx(cfg, ops, {
        "calls": calls, "tokens": calls * n_tok,
        "window_kv_blocks": calls * 4 * 408,
        "window_kv_blocks_unskipped": calls * 4 * 528,
        "routed_assignments": calls * 4 * 4 * n_tok,
        "expert_assignments": calls * 2 * n_tok,
        "experts_touched": calls * 128, "load_max_over_mean_sum": 300.0,
        "sparse_layer_dispatches": calls * 4}, riders)
    assert load_reader("wattn.device_share")(ctx) == pytest.approx(68.0)
    assert load_reader("wattn.kv_blocks_share")(ctx) == pytest.approx(
        100 * 408 / 528)
    assert load_reader("moe.local_share")(ctx) == pytest.approx(12.5)
    assert wattn.pairs(5) == 15 and wattn.pairs(5, window=2) == 3 + 3 * 2
    assert wattn.pairs(4096, 4096) == wattn.pairs(4096)
    # the slice's OWN work: two 16,384-event rows and one of 700
    w_pairs = 4 * (2 * (4096 * 4097 // 2 + (16384 - 4096) * 4096)
                   + 700 * 701 // 2)
    g_pairs = 2 * (16384 * 16385 // 2) + 700 * 701 // 2
    assert wattn.slice_work(ctx, 4096) == (w_pairs // 4, 2 * 16384 + 700)
    assert wattn.slice_work(ctx) == (g_pairs, 2 * 16384 + 700)
    flops = lambda pairs: 2 * 48 * pairs * 2 * 128 / 197e12
    assert load_reader("wattn.roofline")(ctx) == pytest.approx(
        100 * flops(w_pairs) / 0.24)
    assert load_reader("gattn.roofline")(ctx) == pytest.approx(
        100 * flops(g_pairs) / 0.1)
    # the expert layers' readers the benchmark had read this family's
    # counters and op names as they are, over the HELD experts
    assert load_reader("moe.device_share")(ctx) == pytest.approx(24.0)
    assert 0 < load_reader("moe.roofline")(ctx) < 100
    assert load_reader("moe.load_max_over_mean")(ctx) == pytest.approx(0.75)
    for name in NEW:  # a share of a roofline or of the program: under 100
        assert 0 < load_reader(name)(ctx) < 100
    # a slice none of whose dispatches a traced request rode reads nothing
    empty = _ctx(cfg, ops, {"calls": calls}, [(8, other)])
    assert load_reader("wattn.roofline")(empty) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ops_and_counters_reads_nothing(cfg, name):
    """The parent commit, or another packed family: no such op in the
    trace, no such counter in `GET /`."""
    ctx = _ctx(cfg, [("%pio.mla_attention.3", 0.1), ("%fusion.7", 0.2)],
               {"calls": 100, "tokens": 40_000})
    ctx["counters_before"].pop("fastpath.tokens")
    assert load_reader(name)(ctx) is None
    assert load_reader(name)({**ctx, "device_trace": {"modules": {}}}) is None


def test_configuration_holds_every_published_key_but_the_reduced(cfg, bench):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the public catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers",
                       "num_experts"}  # layer_types stays whole
    assert differs <= set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in (
        "num_hidden_layers", "num_dense_layers", "num_experts")}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "weights",
        "event_store", "model_blob"]
    assert entry["source"] == row["source_url"]
    assert cfg["source"].startswith(row["source_url"])
    assert entry["file"] == "benchmark/configs/trinity-large-l5-ep8.json"
    assert 0 < len(entry["why"]) <= 200 and len(cfg["source"]) <= 200
    # no width is cut; the floors of a cut that is still the model
    assert cfg["num_experts"] >= 8 and cfg["items"] == row["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    for text in list(cfg["assumed"].values()) + list(
            cfg["reduced_why"].values()):
        assert "TBD" not in text
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    for key in ("attention_gate", "rope", "norms", "mup", "selection_bias"):
        assert key in cfg["assumed"]


def test_the_cut_fills_the_chip_as_the_file_says(cfg):
    hf = {k: cfg[k] for k in family.MODEL_KEYS}
    hf.update(layer_types=cfg["layer_types"][5:10], num_experts=256,
              num_experts_held=32, vocab_size=cfg["items"])
    mcfg = wm.WindowMoEConfig.from_hf(hf, max_len=16384)
    assert round(mcfg.param_count() * 2 / 1e9, 2) == 10.80
    assert mcfg.n_window_layers == 4 and mcfg.n_moe_layers == 4
    assert cfg["serving"]["max_len"] <= cfg["serving"]["token_ladder"][-1]


def test_the_cell_is_declared_as_the_issue_says(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-l5-ep8", "serve-steady", 1)
    # the fourth of each list; what later PRs added follows (PR 41: a cell)
    assert 0 < len(cell["why"]) <= 200 and bench["workloads"][3] is cell
    assert len(bench["configs"]) >= 4 and len(bench["workloads"]) >= 4
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["serve.p50_ms"]["workloads"][3] == CELL
    assert CELL not in e2e["serve.p95_ms"]["workloads"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | set(SHARED_MOE) <= mine
    assert {"seq.device_ms", "head.device_ms", "seq.pad_share",
            "seq.tokens_per_dispatch", "fastpath.dispatch_ms",
            "fastpath.d2h_ms", "front.self_ms", "batch.turnaround_ms",
            "batch.passes_per_request", "serve.tail_p95_ms.seq",
            "idle.serve.seq", "dispatch.device_ms", "idle.held_share",
            "admit.peak_inflight"} <= mine
    assert not {m for m in mine if m.startswith(("gdn.", "mla.", "score.",
                                                 "attn."))}
    names = [m["name"] for m in bench["per_layer"]]
    # new entries went to the end of their list; what later PRs added
    # follows them (PR 40: `batch.ahead_share`, in every cell; PR 41: the
    # state-space family's four)
    at = names.index(NEW[0])
    assert tuple(names[at:at + 5]) == NEW
    assert names[at + 5] == "batch.ahead_share"
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            # this cell first; a later family that reports the metric too
            # (PR 46: the held experts' share) is appended behind it
            assert m["workloads"][0] == CELL and m["moves"] == "serve.p50_ms"
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))
        elif CELL in m.get("workloads", []):
            # appended behind the cells that were there, nothing else moved;
            # what later PRs appended follows it
            was = [w for w in m["workloads"] if w in (
                "wgde-d128.serve-steady", "joyai-flash-l5.serve-steady",
                "olmo-hybrid-l16.serve-steady")]
            assert m["workloads"][:len(was) + 1] == was + [CELL]


def test_the_gate_is_sized_to_the_cells_rate(cfg):
    """docs/operations.md's rule, p99 x qps + stall seconds x qps, at the
    cell's rate with the longest stall on record (15 s: PERF.md section 7
    Q1)."""
    rate = cfg["knee_rps"] * 0.3  # traffic/serve-steady.json
    assert cfg["serving"]["max_inflight"] >= 1.0 * rate + 15 * rate
    assert "TBD" not in cfg["knee_why"] + cfg["serving"]["max_inflight_why"]
