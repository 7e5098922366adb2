"""The benchmark's sequence-family pieces that can be held on the CPU: its
own reference against the repository's, its cost functions on a case worked
by hand, the trace reducer's name rule, and the configuration file against
the public catalog's keys."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from pio_bench import costs_seq, reference_seq, seeded_seq, xplane_named  # noqa: E402

from predictionio_tpu.models import latent_moe as lm  # noqa: E402
from predictionio_tpu.models.latent_moe_reference import reference_forward  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash-l5.json")


@pytest.fixture(scope="module")
def cfg():
    with open(CONFIG) as f:
        return json.load(f)


def test_the_two_references_agree_under_forced_routing(cfg):
    """Written apart (one imports nothing from the program), they compute
    the same model: f32 rounding only."""
    hf = {k: cfg[k] for k in (
        "first_k_dense_replace", "norm_topk_prob", "routed_scaling_factor",
        "rms_norm_eps", "rope_theta")}
    hf.update(cfg["rehearsal"]["model"], vocab_size=300)
    mcfg = lm.LatentMoEConfig.from_hf(hf, max_len=64)
    P = {k: v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v
         for k, v in lm.init_params(mcfg, 7, std=0.2, bias_std=0.05).items()}
    hist = np.random.default_rng(0).integers(0, 300, 37).astype(np.int32)
    ours = reference_forward(mcfg, P, hist)
    theirs = reference_seq.forward(hf, P, hist, np.asarray(ours["picks"]))
    assert theirs["violation"] == 0.0 and theirs["flipped"] == 0
    np.testing.assert_allclose(theirs["h_last"], ours["h_last"], rtol=2e-5,
                               atol=2e-6)
    # another expert forced into the last position's last layer: it shows
    picks = np.asarray(ours["picks"]).copy()
    unused = next(e for e in range(16) if e not in picks[-1, -1])
    picks[-1, -1, 0] = unused
    forced = reference_seq.forward(hf, P, hist, picks)
    assert forced["flipped"] == 1 and forced["flipped_last"] == 1
    assert forced["violation"] > 0.0


def test_histories_are_seeded_clipped_and_heavy_tailed(cfg):
    h = seeded_seq.make_histories(2 ** 31 + 5, 4096, 500, cfg["history"])
    again = seeded_seq.make_histories(2 ** 31 + 5, 4096, 500, cfg["history"])
    np.testing.assert_array_equal(h.items, again.items)
    lengths = np.diff(h.indptr)
    assert lengths.min() >= 8 and lengths.max() <= 2048
    assert 100 < np.median(lengths) < 160 and lengths.mean() > 170
    assert len(h.recent_indices("u7", 5)) == min(5, lengths[7])
    np.testing.assert_array_equal(h.recent_indices("u7", 10 ** 6), h.of(7, 10 ** 6))
    assert len(h.recent_indices("nobody", 5)) == 0
    assert len(h.recent_indices("u999999", 5)) == 0


def test_cost_functions_on_a_case_worked_by_hand():
    c = costs_seq.expert_products(assignments=16, experts_touched=3,
                                  hidden=4, width=2)
    assert c["flops"] == 2 * 3 * 4 * 2 * 16
    assert c["bytes"] == 3 * 3 * 4 * 2 * 2 + 16 * 2 * 4 * 2
    a = costs_seq.latent_attention(causal_pairs=10, tokens=4, layers=2,
                                   heads=3, d_nope=4, d_rope=2, d_v=4)
    assert a["flops"] == 2 * 2 * 3 * 10 * (4 + 2 + 4)
    assert a["bytes"] == 2 * 4 * (3 * (4 + 2 + 4 + 4 + 4) + 2) * 2


def test_an_op_is_found_by_its_own_name_not_its_operands():
    text = ("%fusion.47 = f32[65536,2048]{1,0} fusion(f32[65536,2048]{1,0} "
            "%pio.moe_experts.8, s32[65536]{0} %x)")
    assert xplane_named.own_name(text) == "%fusion.47"
    assert "moe_experts" in xplane_named.own_name(
        "%pio.moe_experts.8 = f32[2048,768]{1,0} custom-call(...)")


def test_configuration_holds_every_published_key_but_the_reduced(cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the public catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_nextn_predict_layers"}
    assert differs <= set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in sorted(differs)}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"].startswith(row["source_url"])


def test_the_cut_fills_the_chip_as_the_file_says(cfg):
    hf = {k: v for k, v in cfg.items() if isinstance(v, (int, float, bool))}
    n = lm.LatentMoEConfig.from_hf(
        {**hf, "vocab_size": cfg["items"]}).param_count()
    assert round(n * 2 / 1e9, 2) == 11.12


def test_the_gate_is_sized_to_the_cells_rate(cfg):
    """docs/operations.md's rule, p99 x qps + stall seconds x qps, at the
    cell's rate with the longest stall on record (15 s: PERF.md section 7
    Q1); the default 256 was 3.0 s, and the cell's first check was refused
    for 303 requests shed.  The slowest answer behind such a stall (the
    backlog drains at about 300 rows/s net) stays inside the timeout."""
    rate = cfg["knee_rps"] * 0.3  # traffic/serve-steady.json
    gate = cfg["serving"]["max_inflight"]
    assert gate >= 0.13 * rate + 15 * rate
    assert 15 + gate / 300 < cfg["client_timeout_s"]


def test_a_held_dispatch_sheds_nothing_in_the_cell(cfg, capsys, monkeypatch):
    """A rehearsal of the cell at its own rate in which one dispatch holds
    the batcher for 3.5 s: more requests are in flight than the program's
    default gate (256) admits, none is shed, the answers stay correct.
    What `tools/chip_probes/seq_gate_stall.py` does on the chip."""
    import time

    import run as bench_run

    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sound = PackedSequenceScorer.score_topk
    say = bench_run.say
    state = {"go": None, "held": False}

    def say_and_note_the_window(msg):
        say(msg)
        if msg.startswith("set-up done"):
            state["go"] = time.perf_counter()

    def stands_still_once(self, histories, k):
        if (state["go"] is not None and not state["held"]
                and time.perf_counter() - state["go"] >= 0.5):
            state["held"] = True
            time.sleep(3.5)
        return sound(self, histories, k)

    monkeypatch.setattr(bench_run, "say", say_and_note_the_window)
    monkeypatch.setattr(PackedSequenceScorer, "score_topk", stands_still_once)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "joyai-flash-l5.serve-steady", "--seconds",
        "5", "--trace", "0", "--rate", str(cfg["knee_rps"] * 0.3),
        "--rehearse-cpu", "--shrink", "64", "--seed", str(2**31 + 27)])
    assert bench_run.main() == 0
    out = capsys.readouterr().out
    res = json.loads(out.strip().splitlines()[-1])
    assert state["held"]
    assert res["failed"] == 0 and res["correct"] is True
    assert res["attempted"] == 420
    assert int(out.split("peak in flight ")[1].split()[0]) > 256


# what the scorers' set-up leaves in the counters, read as the window opens
SETUP = {"fastpath.compile_s": 9.5, "fastpath.branch_traces": 18,
         "fastpath.branch_calls": 60, "fastpath.compile_count": 6,
         "fastpath.programs_loaded": 6}


@pytest.mark.parametrize("name, before, want", [
    ("setup.compile_s", SETUP, 9.5),
    ("setup.branch_trace_share", SETUP, 30.0),
    # the parent of ISSUE 48 has neither the timer nor the counters, a
    # family whose depth is a scan no counters: the line leaves them out
    ("setup.compile_s", {"fastpath.compile_count": 6}, None),
    ("setup.branch_trace_share", {"fastpath.compile_s": 9.5}, None),
    # ISSUE 49: rungs taken from the program store over rungs made ready;
    # 0 in the run that built the store, nothing on a program without the
    # counter (the parent of ISSUE 49)
    ("setup.program_load_share", SETUP, 100.0),
    ("setup.program_load_share", {**SETUP, "fastpath.programs_loaded": 0},
     0.0),
    ("setup.program_load_share", {"fastpath.compile_count": 6}, None),
], ids=["compile_s", "branch_trace_share", "no-timer", "no-counters",
        "all-loaded", "none-loaded", "no-load-counter"])
def test_the_set_ups_metrics_read_the_counters_before_the_window(
        name, before, want):
    from pio_bench.readers import load_reader

    # whatever the window added is not the set-up's
    after = {k: v + 1 for k, v in before.items()}
    got = load_reader(name)({"counters_before": before,
                             "counters_after": after})
    assert got == (want if want is None else pytest.approx(want))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["moves"] == "setup_s"
    assert "joyai-flash-l5.serve-steady" in entry["workloads"]
