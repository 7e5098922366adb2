"""The benchmark's pieces for the gated-delta-rule hybrid that can be held
on the CPU: its own reference against the repository's, the history lengths
that are fixed per user, its cost functions on a case worked by hand, the
four new per-layer readers on a recorded context, and the configuration
file against the public catalog's keys."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from pio_bench import costs, costs_gdn, peaks, reference_gdn  # noqa: E402
from pio_bench.engines import gdn_hybrid_sequence as family  # noqa: E402
from pio_bench.readers import load_reader  # noqa: E402

from predictionio_tpu.models import gdn_hybrid as gh  # noqa: E402
from predictionio_tpu.models.gdn_hybrid_reference import reference_forward  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b-l16.json")
CELL = "olmo-hybrid-l16.serve-steady"
NEW = ("gdn.device_share", "gdn.roofline", "attn.roofline", "gdn.chunk_fill")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_two_references_agree(cfg):
    """Written apart (one imports nothing from the program), they compute
    the same model: f32 rounding only; and the control differs."""
    hf = family.model_config(cfg)  # the rehearsal's widths off the chip
    hf["vocab_size"] = 300
    mcfg = gh.GDNHybridConfig.from_hf(hf, max_len=64)
    P = {k: v.astype(jnp.float32) for k, v in gh.init_params(mcfg, 7).items()}
    hist = np.random.default_rng(0).integers(0, 300, 37).astype(np.int32)
    ours = np.asarray(reference_forward(mcfg, P, hist)["h_last"])
    theirs = reference_gdn.forward(hf, P, hist)
    np.testing.assert_allclose(theirs, ours, rtol=2e-5, atol=2e-6)
    rows = [{"history": hist, "h_last": ours}]
    assert reference_gdn.compare_trunk(hf, P, rows)["h_last_rel_err"] < 1e-5
    assert reference_gdn.compare_trunk(
        hf, P, rows, normalize_qk=False)["h_last_rel_err"] > 0.1
    assert reference_gdn.period_of(hf["layer_types"]) == tuple(
        hf["layer_types"][:4])


def test_history_lengths_are_fixed_per_user_and_evenly_spread(cfg):
    spec = cfg["history"]
    a = family.make_histories(2 ** 31 + 5, 4096, 500, spec)
    b = family.make_histories(2 ** 31 + 6, 4096, 500, spec)
    np.testing.assert_array_equal(a.indptr, b.indptr)  # not the seed's
    assert (a.items[:1000] != b.items[:1000]).any()  # the ids are
    lengths = np.diff(a.indptr)
    assert lengths.min() >= 8 and lengths.max() <= 2048
    assert 100 < np.median(lengths) < 160 and lengths.mean() > 170
    # any run of consecutive users carries nearly the same work: the means
    # of 64 windows of 64 users differ by a few percent (independent draws
    # from this law: the standard deviation of such a mean is ~16 %)
    means = lengths.reshape(64, 64).mean(axis=1)
    assert means.std() / means.mean() < 0.06
    shifted = np.roll(lengths, 31).reshape(64, 64).mean(axis=1)
    assert shifted.std() / shifted.mean() < 0.08
    assert len(a.recent_indices("u7", 5)) == min(5, lengths[7])


def test_the_token_ladder_is_the_deployments_not_the_yardsticks(cfg):
    """256 tokens doubling to 8,192 (ISSUE 34): a history of up to 256
    events runs alone at the lowest rung, and the longest history fits the
    top one four times over.  The rehearsal's ladder doubles too."""
    ladder = cfg["serving"]["token_ladder"]
    assert ladder == [256 * 2 ** i for i in range(6)]
    assert ladder[-1] == 4 * cfg["serving"]["max_len"]
    small = cfg["rehearsal"]["serving"]["token_ladder"]
    assert all(b == 2 * a for a, b in zip(small, small[1:]))


def test_cost_functions_on_a_case_worked_by_hand():
    c = costs_gdn.gated_delta_scan(tokens=10, rows=2, heads=3, d_k=4, d_v=8)
    assert c["flops"] == 7 * 10 * 3 * 4 * 8
    assert c["bytes"] == 10 * 3 * ((2 * 4 + 2 * 8) * 2 + 8)
    assert c["states"] == 6
    a = costs_gdn.causal_attention(causal_pairs=10, tokens=4, layers=2,
                                   heads=3, d_head=8)
    assert a["flops"] == 2 * 2 * 3 * 10 * 16
    assert a["bytes"] == 2 * 4 * 3 * 4 * 8 * 2


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
            "peaks": peaks.PEAKS["TPU v5 lite"], "costs": costs}


def test_the_new_readers_on_a_recorded_context(cfg):
    ops = ([(f"%pio.gdn_scan.{i}", 0.010) for i in range(12)]
           + [(f"%pio.packed_attention.{i}", 0.005) for i in range(4)]
           + [("%fusion.7", 0.2), ("%pio.score_topk.1", 0.01)])
    ctx = _ctx(cfg, ops, {
        "calls": 100, "tokens": 40_000, "causal_pairs": 100 * 200 * 201 // 2,
        "scan_tokens": 12 * 40_000, "scan_rows": 12 * 200,
        "scan_chunks": 12 * 100 * 8, "scan_chunk": 64})
    assert load_reader("gdn.device_share")(ctx) == pytest.approx(24.0)
    assert load_reader("gdn.chunk_fill")(ctx) == pytest.approx(
        100 * 40_000 / (100 * 512))
    # 400 tokens a dispatch a layer: 12 x 400 x 30 x 1,160 B at 819 GB/s
    least = 12 * 400 * 30 * ((2 * 96 + 2 * 192) * 2 + 8) / 819e9
    assert load_reader("gdn.roofline")(ctx) == pytest.approx(
        100 * least * 10 / 0.12)
    pairs = 200 * 201 / 2
    flops = 2 * 4 * 30 * pairs * 2 * 128
    byts = 4 * 400 * 30 * 4 * 128 * 2
    assert load_reader("attn.roofline")(ctx) == pytest.approx(
        100 * max(flops / 197e12, byts / 819e9) * 10 / 0.02)
    for name in NEW:  # a share of a roofline or of the program: under 100
        assert 0 < load_reader(name)(ctx) < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_ops_and_counters_reads_nothing(cfg, name):
    """The parent commit, or the other packed family: no such op in the
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
                   if r["name"] == "Olmo-Hybrid-7B")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"}  # layer_types stays whole
    assert differs <= set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in sorted(differs)}
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "weights", "event_store", "model_blob"]
    assert entry["source"].startswith(row["source_url"])
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b-l16.json"
    assert 0 < len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_cut_fills_the_chip_as_the_file_says(cfg):
    hf = {k: cfg[k] for k in family.MODEL_KEYS}
    hf["layer_types"] = hf["layer_types"][:hf["num_hidden_layers"]]
    mcfg = gh.GDNHybridConfig.from_hf({**hf, "vocab_size": cfg["items"]})
    assert round(mcfg.param_count() * 2 / 1e9, 2) == 8.20
    assert mcfg.n_periods == 4 and mcfg.n_linear_layers == 12
    assert cfg["serving"]["max_len"] <= cfg["serving"]["token_ladder"][-1]


def test_the_cell_is_declared_as_the_issue_says(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b-l16", "serve-steady", 1)
    assert 0 < len(cell["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve.p50_ms"]["workloads"]
    assert CELL not in e2e["serve.p95_ms"]["workloads"]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= mine
    assert {"seq.device_ms", "head.device_ms", "seq.pad_share",
            "seq.tokens_per_dispatch", "fastpath.dispatch_ms",
            "fastpath.d2h_ms", "front.self_ms", "batch.turnaround_ms",
            "batch.passes_per_request", "serve.tail_p95_ms.seq",
            "idle.serve.seq"} <= mine
    assert not {m for m in mine if m.startswith(("moe.", "mla.", "score."))}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve.p50_ms"
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))


def test_the_gate_is_sized_to_the_cells_rate(cfg):
    """docs/operations.md's rule, p99 x qps + stall seconds x qps, at the
    cell's rate with the longest stall on record (15 s: PERF.md section 7
    Q1); and the slowest answer behind such a stall stays inside the
    client's timeout."""
    rate = cfg["knee_rps"] * 0.3  # traffic/serve-steady.json
    gate = cfg["serving"]["max_inflight"]
    assert gate >= 0.3 * rate + 15 * rate
    assert 15 + gate / (cfg["knee_rps"] * 0.5) < cfg["client_timeout_s"]
