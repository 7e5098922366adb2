"""The latent-attention sparse-expert family at a small size on the CPU:
the packed serving program against the plain reference, the routing rules,
and the template through ``QueryServer(batching=True)``.

Tolerances, and why each:

* ``F32_TOL`` 2e-5 (relative): on f32 weights the program and the reference
  compute the same f32 sums in another order (online softmax by blocks,
  grouped products, XLA's own reductions); five layers of that stay near
  1e-6, twenty times under the limit.  No routing flip can occur: the
  seeded scores' closest 8th-vs-9th gap is far above 1e-6.
* ``BF16_TOL`` 6e-2 (relative L2 of ``h_last``): bf16 operands round to 3
  significant digits; with the test's large weights (std 0.2, so every
  layer matters) the forced-routing error measures 1-3e-2.  The routing is
  FORCED to the program's picks, and those picks must be admissible:
  ``violation`` (how far below the reference's 8th-best score+bias a pick
  lies) under ``ROUTE_TOL`` 3e-2, where a wrong expert is off by 0.1-1.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fingerprints
import shared_branch_cases as branches

from predictionio_tpu.models import latent_moe as lm
from predictionio_tpu.models.latent_moe_reference import (
    _rope, reference_forward,
)
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.latent_attention import mla_attention

F32_TOL, BF16_TOL, ROUTE_TOL = 2e-5, 6e-2, 3e-2

HF = dict(
    vocab_size=300, hidden_size=64, num_hidden_layers=3, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    n_shared_experts=1, first_k_dense_replace=1, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    rope_theta=32000000, rope_interleave=True, rope_scaling=None,
    rms_norm_eps=1e-6,
)
CFG = lm.LatentMoEConfig.from_hf(HF, max_len=64)
K = 10


@pytest.fixture(scope="module")
def weights():
    bf = lm.init_params(CFG, 3_000_000_007, std=0.2, bias_std=0.05)
    return {"bf16": bf,
            "f32": {k: v.astype(jnp.float32) for k, v in bf.items()}}


@pytest.fixture(scope="module")
def program():
    return jax.jit(lambda P, b: lm.forward_packed(
        CFG, P, b["tokens"], b["positions"], b["seg_start"], b["valid"],
        b["last_idx"], K))


def _histories(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lengths]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


RAGGED = [(5, 20, 17, 3), (64,), (1, 1, 2), (31, 33), (8, 8, 8, 8, 8, 8, 8)]


@pytest.mark.parametrize("lengths", RAGGED, ids=lambda x: "-".join(map(str, x)))
def test_packed_program_meets_the_reference_on_f32_weights(
        weights, program, lengths):
    hists = _histories(1, lengths)
    out = program(weights["f32"], lm.pack(hists, 64, 8))
    for r, h in enumerate(hists):
        ref = reference_forward(CFG, weights["f32"], h)
        assert _rel(out["h_last"][r], ref["h_last"]) < F32_TOL
        # the head's top-k on the device: the reference's k best logits
        want = np.sort(np.asarray(ref["logits"]))[::-1][:K]
        np.testing.assert_allclose(out["values"][r], want, rtol=1e-4,
                                   atol=1e-5)
        got = np.asarray(ref["logits"])[np.asarray(out["indices"][r])]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("lengths", RAGGED[:3],
                         ids=lambda x: "-".join(map(str, x)))
def test_packed_equals_one_by_one(weights, program, lengths):
    hists = _histories(2, lengths)
    packed = program(weights["f32"], lm.pack(hists, 64, 8))
    for r, h in enumerate(hists):
        alone = program(weights["f32"], lm.pack([h], 64, 8))
        assert _rel(packed["h_last"][r], alone["h_last"][0]) < F32_TOL
        np.testing.assert_array_equal(packed["indices"][r],
                                      alone["indices"][0])


@pytest.mark.parametrize("lengths", RAGGED[:2],
                         ids=lambda x: "-".join(map(str, x)))
def test_bf16_program_meets_the_reference_under_its_own_routing(
        weights, program, lengths):
    hists = _histories(3, lengths)
    batch = lm.pack(hists, 64, 8)
    out = program(weights["bf16"], batch)
    for r, h in enumerate(hists):
        lo, hi = batch["seg_start"][batch["last_idx"][r]], batch["last_idx"][r]
        forced = reference_forward(
            CFG, weights["bf16"], h,
            picks=np.asarray(out["picks"])[:, lo:hi + 1])
        assert float(forced["violation"].max()) < ROUTE_TOL
        assert _rel(np.asarray(out["h_last"][r], np.float32),
                    forced["h_last"]) < BF16_TOL


def test_padded_tokens_reach_no_expert(weights, program):
    hists = _histories(4, (5, 9))
    out = program(weights["bf16"], lm.pack(hists, 64, 8))
    counts = np.asarray(out["expert_counts"])
    assert counts.shape == (CFG.n_moe_layers, CFG.n_routed_experts)
    assert (counts.sum(axis=1) == 14 * CFG.num_experts_per_tok).all()


def _router(bias):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    w_gate = jnp.asarray(rng.standard_normal((64, 16)) * 0.1, jnp.float32)
    return x, w_gate, moe.route_sigmoid_topk(
        x, w_gate, jnp.asarray(bias, jnp.float32), top_k=4, scale=2.5)


def test_the_bias_selects_and_never_weighs():
    bias = np.zeros(16, np.float32)
    _, _, (picked0, w0, sigma) = _router(bias)
    loser = int(np.argmin(np.asarray(sigma).mean(axis=0)))
    bias[loser] = 10.0  # now the worst-scoring expert wins every selection
    _, _, (picked, w, sigma1) = _router(bias)
    np.testing.assert_array_equal(sigma, sigma1)
    assert (np.asarray(picked) == loser).any(axis=1).all()
    assert not (np.asarray(picked0) == loser).any(axis=1).all()
    # the weights are the UNBIASED scores of the picked, normalised, x 2.5
    s = np.take_along_axis(np.asarray(sigma), np.asarray(picked), 1)
    np.testing.assert_allclose(
        w, 2.5 * s / s.sum(axis=1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-6)


def test_no_token_is_dropped_under_a_skew_that_overflows_any_capacity(
        weights):
    """Every token picks the SAME four experts: a capacity of 1.25 x
    (tokens x 4 / 16) rows an expert would drop 11 of every 16."""
    P = dict(weights["f32"])
    bias = np.zeros(16, np.float32)
    bias[[2, 3, 5, 7]] = 10.0
    for i in range(CFG.first_k_dense_replace, CFG.num_hidden_layers):
        P[f"L{i}.gate_bias"] = jnp.asarray(bias)
    hists = _histories(6, (40, 24))
    out = jax.jit(lambda P, b: lm.forward_packed(
        CFG, P, b["tokens"], b["positions"], b["seg_start"], b["valid"],
        b["last_idx"], K))(P, lm.pack(hists, 64, 8))
    counts = np.asarray(out["expert_counts"])
    assert (counts[:, [2, 3, 5, 7]] == 64).all()
    assert counts.sum() == CFG.n_moe_layers * 64 * 4
    for r, h in enumerate(hists):
        ref = reference_forward(CFG, P, h)
        assert _rel(out["h_last"][r], ref["h_last"]) < F32_TOL


def test_the_shared_expert_is_counted_once(weights):
    """With every routed expert's output projection zeroed, what is left of
    a sparse layer is exactly one shared SwiGLU."""
    P = dict(weights["f32"])
    p = f"L{CFG.first_k_dense_replace}."
    P[p + "e_w2"] = jnp.zeros_like(P[p + "e_w2"])
    x = jnp.asarray(np.random.default_rng(7).standard_normal((16, 64)),
                    jnp.float32)
    y, _, _ = lm._sparse_ffn(
        CFG, None, lm.layer_weights(P, CFG.first_k_dense_replace,
                                    lm.SPARSE_FFN), x, None)
    xn = lm.rms_norm(x, P[p + "ffn_norm"], CFG.rms_norm_eps)
    want = lm._swiglu(xn, P[p + "s_w1"], P[p + "s_w3"], P[p + "s_w2"])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_interleaved_rope_is_the_reference_rotation():
    x = jnp.asarray(np.random.default_rng(8).standard_normal((3, 11, 8)),
                    jnp.float32)
    got = lm.rope_interleaved(x, jnp.arange(11), 32e6)
    np.testing.assert_allclose(got, _rope(x, 32e6), rtol=1e-5, atol=1e-6)
    # position 0 is the identity; a pair's norm never changes
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(
        np.hypot(got[..., 0::2], got[..., 1::2]),
        np.hypot(x[..., 0::2], x[..., 1::2]), rtol=1e-5)


@pytest.mark.parametrize("block", [16, 32, 64])
def test_latent_attention_kernel_is_block_diagonal_causal(block):
    rng = np.random.default_rng(9)
    h, t, dn, dr, dv = 4, 64, 16, 8, 16
    qn, kn = (jnp.asarray(rng.standard_normal((h, t, dn)), jnp.float32)
              for _ in range(2))
    qr = jnp.asarray(rng.standard_normal((h, t, dr)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((t, dr)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((h, t, dv)), jnp.float32)
    seg = np.arange(t, dtype=np.int32)  # the tail: padded tokens, each alone
    at = 0
    for n in (5, 20, 17, 3):
        seg[at:at + n] = at
        at += n
    out = mla_attention(qn, qr, kn, kr, v, jnp.asarray(seg), scale=0.3,
                        block=block)
    s = (jnp.einsum("hqd,hkd->hqk", qn, kn)
         + jnp.einsum("hqd,kd->hqk", qr, kr)) * 0.3
    pos = np.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] >= seg[:, None])
    want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
        jnp.where(mask[None], s, -jnp.inf), -1), v)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_config_refuses_a_mechanism_it_does_not_implement():
    with pytest.raises(ValueError, match="scoring_func"):
        lm.LatentMoEConfig.from_hf({**HF, "scoring_func": "softmax"})
    with pytest.raises(ValueError, match="n_group"):
        lm.LatentMoEConfig.from_hf({**HF, "n_group": 8})


def test_published_cut_counts_the_parameters_the_issue_states():
    full = dict(HF, vocab_size=129280, hidden_size=2048, num_hidden_layers=5,
                intermediate_size=7168, moe_intermediate_size=768,
                n_routed_experts=256, num_experts_per_tok=8,
                num_attention_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    n = lm.LatentMoEConfig.from_hf(full).param_count()
    assert abs(n - 5.558e9) < 0.005e9  # 11.12 GB in bf16


# -- the scorer and the template ------------------------------------------------


def test_the_packed_scorer_marks_its_launch_inside_device_compute(
        weights, monkeypatch):
    """ISSUE 37: `pio.launch(seq=, rung=)` is the jitted call's return (the
    enqueue), nested in `pio.device_compute`, which carries the rung too."""
    from predictionio_tpu.obs import tracing
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K, ladder=(32, 64),
                              max_rows=4)
    seen, real = [], tracing.annotation
    monkeypatch.setattr(
        tracing, "annotation",
        lambda name, **kv: seen.append((name, kv)) or real(name, **kv))
    rec = tracing.Dispatch(5, False, 2, 0, t_run=0.0, collect_s=0.0,
                           slow_after_s=2.0)
    with tracing.scope((), dispatch=rec):
        sc.score_topk(_histories(11, (5, 20)), 5)
    ids = {"seq": 5, "rung": 32}
    assert seen == [("pio.batch_assembly", ids), ("pio.h2d", ids),
                    ("pio.device_compute", ids), ("pio.launch", ids),
                    ("pio.d2h", ids)]
    assert rec.rung == 32 and rec.stages["device_compute"] > 0


def test_scorer_compiles_ahead_and_never_again(weights):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K,
                              ladder=(32, 64, 128), max_rows=4)
    assert sc.compile_count == 3 and sc.warmup_executions == 3
    hists = _histories(10, (5, 20, 17, 3, 60, 64, 20))  # 3 dispatches
    idx, vals = sc.score_topk(hists, 5)
    assert idx.shape == (7, 5) and sc.compile_count == 3
    for r, h in enumerate(hists):
        ref = np.asarray(reference_forward(CFG, weights["f32"], h)["logits"])
        np.testing.assert_allclose(vals[r], np.sort(ref)[::-1][:5],
                                   rtol=1e-4, atol=1e-5)
    st = sc.stats()
    assert st["calls"] == 3 and st["queries"] == 7
    assert st["tokens"] == 189
    assert st["tokens"] + st["padded_tokens"] == sum(
        int(t) * n for t, n in st["bucket_hits"].items())
    assert st["expert_assignments"] == 189 * 4 * CFG.n_moe_layers
    assert st["sparse_layer_dispatches"] == 3 * CFG.n_moe_layers
    with pytest.raises(ValueError, match="exceeds"):
        sc.score_topk(hists, K + 1)
    # the head's score tile, as the kernel's own rule gives it; there is
    # none to report on the reference backend (the CPU's `auto`)
    assert st["block_items"] is None
    head = weights["f32"]["head"]
    fused = PackedSequenceScorer(CFG, weights["f32"], max_k=K, ladder=(64,),
                                 max_rows=4, backend="fused")
    assert fused.stats()["block_items"] == {"4": {
        "tile_rows": 8, "block_items": min(head.shape[0], 4096)}}
    fi, fv = fused.score_topk(hists[:2], 5)
    np.testing.assert_array_equal(fi, idx[:2])


def _http(url, body=None):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read().decode())


@pytest.fixture()
def served(storage):
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.templates.sequentialrecommendation import (
        SequentialRecommendationEngine,
    )

    store_mod.set_storage(storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "seqapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(11)
    events, t = [], 0
    for u in range(6):
        for i in rng.integers(0, 40, size=3 + 4 * u):
            t += 1
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                event_time=f"2026-01-01T00:{t // 60:02d}:{t % 60:02d}Z"))
    le.batch_insert(events, app_id)
    engine = SequentialRecommendationEngine.apply()
    hf = {k: v for k, v in HF.items() if k != "vocab_size"}
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "seqapp"}},
        "algorithms": [{"name": "latentmoe", "params": {
            "appName": "seqapp", "modelConfig": hf, "maxLen": 16, "seed": 5,
            "tokenLadder": [32, 64], "maxRows": 4, "maxK": 8}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "seq", storage=storage, ctx=ctx)
    qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
    yield qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
    qs.stop()
    store_mod.set_storage(None)


def test_template_serves_through_the_batcher_and_the_resident_program(served):
    from predictionio_tpu.templates.sequentialrecommendation import (
        EventStoreHistory,
    )

    qs, base = served
    ready = _http(base + "/readyz")
    assert ready["fastpathWarm"] is True
    fp = _http(base + "/")["fastpath"][0]
    assert fp["family"] == "latent_moe_sequence"
    assert fp["compile_count"] == 2 and fp["calls"] == 0
    # the batcher cuts a batch anywhere, not at the ALS row ladder's rungs
    assert qs._batcher.buckets == (1, 2, 3, 4, 64)
    model = qs._deployed.models[0]
    for u, num in ((0, 3), (5, 8), (3, 4)):
        ans = _http(base + "/queries.json", {"user": f"u{u}", "num": num})
        scores = [s["score"] for s in ans["itemScores"]]
        assert len(scores) == num and scores == sorted(scores, reverse=True)
        hist = EventStoreHistory("seqapp", ("view", "buy", "rate")
                                 ).recent_indices(f"u{u}", 16, model.item_map)
        assert len(hist) == min(16, 3 + 4 * u)
        got = [model.item_map[s["item"]] for s in ans["itemScores"]]
        forced = reference_forward(
            model.config, model.params, hist,
            picks=qs._deployed.algorithms[0]._scorer(model).forward(
                [hist])["picks"][:, :len(hist)])
        # bf16 weights: the served scores against the forced reference's
        want = np.asarray(forced["logits"], np.float64)
        np.testing.assert_allclose(scores, want[got], atol=BF16_TOL * np.abs(
            want).max())
        assert float(forced["violation"].max()) < ROUTE_TOL
    assert _http(base + "/queries.json",
                 {"user": "nobody", "num": 3}) == {"itemScores": []}
    after = _http(base + "/")
    assert after["fastpath"][0]["compile_count"] == 2  # nothing compiled
    assert after["fastpath"][0]["calls"] == 3
    assert after["batching"]["batches"] >= 3
    recs = _http(base + "/trace/dispatches.json")["dispatches"]
    assert recs[-1]["rung"] in (32, 64)
    assert recs[-1]["stagesMs"]["device_compute"] > 0


def test_train_refuses_a_published_width_rather_than_serve_noise():
    from predictionio_tpu.templates.sequentialrecommendation import (
        LatentMoEAlgorithm, LatentMoEParams,
    )

    algo = LatentMoEAlgorithm(LatentMoEParams(modelConfig=dict(
        HF, hidden_size=2048, n_routed_experts=256, vocab_size=129280)))
    pd = type("PD", (), {"interactions": type("I", (), {
        "n_items": 100, "item_map": None})(), "histories": None})()
    with pytest.raises(NotImplementedError, match="no trainer"):
        algo.train(None, pd)


def test_sasrec_history_goes_through_the_same_seam():
    from predictionio_tpu.templates import sequentialrecommendation as t

    class Provider:
        def recent_items(self, user, limit):
            return [f"{user}-{limit}"]

    algo = t.SASRecAlgorithm(t.SASRecParams())
    algo._histories = lambda model=None: Provider()
    assert algo._history("u1", 7) == ["u1-7"]
    assert isinstance(t.SASRecAlgorithm(t.SASRecParams())._histories(),
                      t.EventStoreHistory)
    model = dataclasses.make_dataclass("M", ["histories"])(Provider())
    assert isinstance(t.LatentMoEAlgorithm(t.LatentMoEParams())._histories(
        model), Provider)


@pytest.mark.parametrize("ladders, want", [
    ((), (1, 8, 64)),                      # no algorithm: the default
    ((None,), (1, 8, 64)),                 # ALS states none
    (((1, 2, 3),), (1, 2, 3)),             # the one algorithm's own
    (((1, 2, 3), (1, 2, 3)), (1, 2, 3)),   # several that agree
    (((1, 2, 3), None), (1, 8, 64)),       # every algorithm runs every
    (((1, 2, 3), (1, 4)), (1, 8, 64)),     # batch: no agreement, no change
])
def test_the_batchers_cut_follows_the_algorithms_only_when_they_agree(
        ladders, want):
    from types import SimpleNamespace

    from predictionio_tpu.serving.query_server import _batch_buckets

    algos = [SimpleNamespace(batch_row_ladder=lad) if lad else object()
             for lad in ladders]
    assert _batch_buckets(algos, (1, 8, 64)) == want


# -- this family's program does not run in token tiles (PR 42) -------------------


@pytest.mark.parametrize("t", fingerprints.RUNGS["latent_moe"])
def test_every_rungs_program_is_the_parents_jaxpr_for_jaxpr(t):
    """The window family runs its dense sublayers in token tiles from 2,048
    tokens on (``ops/token_tiles``) and imports this module's ``_mm``,
    ``_swiglu`` and ``rms_norm``; this one is left as it was — a dispatch
    here is the experts' weights from HBM — at EVERY rung, by its
    fingerprint: the text as PR 48 left it (the layers' equal branches one
    ``pjit`` each; the equations are PR 41's, held to the bit below)."""
    assert fingerprints.fingerprint("latent_moe", CFG, t) == \
        fingerprints.PARENT[f"latent_moe.{t}"]


# -- equal residual branches are one traced and lowered function (PR 48) ---------


@pytest.mark.parametrize("what, t, kw", [
    ("counts", 64, dict(distinct=3, calls=2 * CFG.num_hidden_layers)),
    ("bits", 64, {}), ("bits", 128, {}),
    ("lowered", 256, dict(kernels=4, unshared_kernels=9)),
])
def test_the_layers_share_their_equal_branches(what, t, kw, weights,
                                               monkeypatch):
    """One dense layer of three: attention is ONE branch for all three, the
    dense and the sparse feed-forward one each — 3 bodies traced for 2 x 3
    calls, and of the kernels one attention and the sparse branch's three
    grouped products where the layers' own come to 3 + 2 x 3."""
    branches.check(what, monkeypatch, lm, CFG, weights["bf16"], t, **kw)


def test_the_scorer_reports_no_tiles_for_a_family_that_runs_none(weights):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K, ladder=(64,),
                              max_rows=4)
    sc.score_topk(_histories(10, (5, 20)), 5)
    assert not {"dense_tile", "dense_tiles", "dense_tiles_rung"} & set(
        sc.stats())
