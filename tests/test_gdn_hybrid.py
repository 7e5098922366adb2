"""The gated-delta-rule / full-attention hybrid at a small size on the CPU:
the chunked scan against the token-by-token recurrence, packing, the state
carry, the packed serving program against the plain reference, and both
packed families through ONE scorer class and the template.

Tolerances, and why each:

* ``SCAN_TOL`` 2e-5 (absolute, outputs of size ~0.5): on f32 inputs the
  chunked form computes the recurrence's f32 sums in another order (a
  triangular inverse by doubling, three products a chunk); readings are
  1-6e-7 at these sizes.  ``STATE_TOL`` 2e-5 of states of size ~3.
* ``F32_TOL`` 5e-5 (relative L2 of ``h_last`` / of the logits' largest): on
  f32 weights the program and the reference compute the same sums in
  another order; eight post-normed layers stay at 1-4e-6.
* ``BF16_TOL`` 0.15 (relative L2 of ``h_last``): bf16 operands round to 3
  significant digits and every sublayer's OUTPUT is normed (``x +
  Norm(f(x))``), so a relative error is handed on undamped: eight layers at
  hidden 64, where no average over a long contraction helps, read
  0.02-0.08.  The control — the q/k normalisation left out of the
  reference — reads above 0.5 and must fail even this.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import gdn_hybrid as gh
from predictionio_tpu.models import latent_moe as lm
from predictionio_tpu.models.gdn_hybrid_reference import (
    _conv, gated_delta_recurrence, reference_forward,
)
from predictionio_tpu.ops import gated_delta as gd
from predictionio_tpu.ops.flash_attention import packed_causal_attention

SCAN_TOL, STATE_TOL, F32_TOL, BF16_TOL = 2e-5, 2e-5, 5e-5, 0.15

HF = dict(
    vocab_size=300, hidden_size=64, intermediate_size=96,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rms_norm_eps=1e-6, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    rope_parameters={"rope_theta": None},
)
CFG = gh.GDNHybridConfig.from_hf(HF, max_len=64)
K = 10
H, DK, DV = 2, 16, 32


def _scan_inputs(seed, t):
    r = np.random.default_rng(seed)
    q, k = r.normal(size=(2, H, t, DK))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(H, t, DV))
    g = -np.exp(r.uniform(-6, 1, size=(H, t)))  # decays 0.07 ... 0.998
    beta = 2 / (1 + np.exp(-r.normal(size=(H, t))))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


def _seg_start(lens):
    starts = np.cumsum([0] + list(lens[:-1]))
    return np.concatenate(
        [np.full(n, s) for n, s in zip(lens, starts)]).astype(np.int32)


def _one_by_one(args, lens, s0=None):
    """Each row alone through the token-by-token recurrence."""
    outs, finals, at = [], [], 0
    for r, n in enumerate(lens):
        o, s = gated_delta_recurrence(
            *(a[:, at:at + n] for a in args),
            s0=None if s0 is None else s0[r])
        outs.append(o)
        finals.append(s)
        at += n
    return jnp.concatenate(outs, axis=1), jnp.stack(finals)


# -- (a) the scan against the recurrence ---------------------------------------


@pytest.mark.parametrize("t, chunk", [(64, 16), (96, 32), (128, 64),
                                      (40, None), (64, 8)])
def test_chunked_scan_equals_the_recurrence(t, chunk):
    # one history as long as the axis: t = 40 is not a multiple of 64 (the
    # chunk becomes the axis), 96 = 3 x 32, and 64 / 8 crosses 8 chunks
    args = _scan_inputs(t, t)
    want, _ = gated_delta_recurrence(*args)
    got = gd.gdn_scan(*args, jnp.zeros(t, jnp.int32), chunk=chunk,
                      interpret=True)
    assert float(jnp.abs(got - want).max()) < SCAN_TOL
    with pytest.raises(ValueError, match="multiple"):
        gd.gdn_scan(*args, jnp.zeros(t, jnp.int32), chunk=7)


@pytest.mark.parametrize("t, chunk", [(256, 64), (128, 16)])
def test_the_state_is_carried_in_f32_from_chunk_to_chunk(t, chunk):
    """Heads that hardly decay (0.999 a token) over a history of several
    chunks: what a chunk hands on is still most of the state many chunks
    later.  On f32 inputs the kernel stays at the recurrence's rounding;
    the control — the same recurrence a chunk at a time, its state rounded
    to bf16 at every chunk's end — reads 60-70 times the limit.  (The
    benchmark's comparison cannot tell the two apart: at bf16 operands the
    model's ``h_last`` reads the same either way.  This test can.)"""
    q, k, v, _, beta = _scan_inputs(t, t)
    g = jnp.full((H, t), -1e-3, jnp.float32)
    want, _ = gated_delta_recurrence(q, k, v, g, beta)
    got = gd.gdn_scan(q, k, v, g, beta, jnp.zeros(t, jnp.int32), chunk=chunk,
                      interpret=True)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < SCAN_TOL * scale
    outs, state = [], None
    for at in range(0, t, chunk):
        o, state = gated_delta_recurrence(
            *(a[:, at:at + chunk] for a in (q, k, v, g, beta)), s0=state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(o)
    low = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(low - want).max()) > 30 * SCAN_TOL * scale


# -- (b) packed against one by one ---------------------------------------------


@pytest.mark.parametrize("lens, chunk", [
    ((5, 1, 30, 20) + (1,) * 8, 16),  # mid-chunk starts, a one-token row,
    ((40, 3, 53), 32),                # and the padded tail of one-token rows
    ((70, 1, 57), 64),
    ((16, 16, 32), 16),               # starts ON chunk boundaries
])
def test_packed_scan_equals_each_row_alone(lens, chunk):
    t = sum(lens)
    args = _scan_inputs(t + 1, t)
    want, _ = _one_by_one(args, lens)
    got = gd.gdn_scan(*args, jnp.asarray(_seg_start(lens)), chunk=chunk,
                      interpret=True)
    assert float(jnp.abs(got - want).max()) < SCAN_TOL


# -- (b') the padded tail is not run --------------------------------------------

REAL = (30, 1, 25)  # 56 tokens: three whole chunks of 16 and half a fourth


def _with_tail(tail_chunks, chunk=16):
    """REAL's rows, the part-filled last chunk's padding, then ``tail_chunks``
    whole chunks of padding: one-token histories, as ``pack`` lays them."""
    n_real = sum(REAL)
    t = -(-n_real // chunk) * chunk + tail_chunks * chunk
    args = _scan_inputs(11, 56 + 3 * chunk + 8)  # the same draws at every t
    lens = REAL + (1,) * (t - n_real)
    return [a[:, :t] for a in args], jnp.asarray(_seg_start(lens)), n_real, t


@pytest.mark.parametrize("tail_chunks", [0, 1, 3])
def test_split_scan_equals_the_recurrence_before_a_padded_tail(tail_chunks):
    args, seg, n_real, t = _with_tail(tail_chunks)
    want, _ = _one_by_one([a[:, :n_real] for a in args], REAL)
    got = gd.gdn_scan(*args, seg, chunk=16, n_real=jnp.int32(n_real),
                      interpret=True)
    assert got.shape == (H, t, DV)
    assert float(jnp.abs(got[:, :n_real] - want).max()) < SCAN_TOL
    # the chunks past the last real token are not run: zeros, not garbage
    assert bool(jnp.isfinite(got).all())
    assert not bool(got[:, 64:].any())
    assert gd.scan_chunks(t, 16, n_real=n_real) == 4
    assert gd.scan_chunks(t, 16) == 4 + tail_chunks


@pytest.mark.parametrize("tail_chunks", [1, 3])
def test_real_rows_do_not_change_by_a_bit_with_the_padded_tail(tail_chunks):
    short, seg0, n_real, _ = _with_tail(0)
    alone = gd.gdn_scan(*short, seg0, chunk=16, interpret=True)
    args, seg, _, _ = _with_tail(tail_chunks)
    skipped = gd.gdn_scan(*args, seg, chunk=16, n_real=n_real, interpret=True)
    scanned = gd.gdn_scan(*args, seg, chunk=16, interpret=True)
    for got in (skipped, scanned):
        np.testing.assert_array_equal(got[:, :n_real], alone[:, :n_real])
    # a padded token that IS scanned is a history of its own: o = b (k.q) v
    assert bool(jnp.isfinite(scanned).all()) and bool(scanned[:, 64:].any())


def test_counters_count_the_chunks_that_ran():
    own = gh.DispatchCounters(CFG)
    layers = CFG.n_linear_layers
    own.add(256, 1, 100, {})   # two of four chunks hold a real token
    own.add(256, 2, 256, {})
    own.add(512, 1, 257, {})   # five of eight
    st = own.stats()
    assert st["scan_chunks"] == layers * (2 + 4 + 5)
    assert st["scan_tokens"] == layers * (100 + 256 + 257)


@pytest.mark.parametrize("chunk", [64, 16])
def test_the_prepass_inverts_the_chunks_triangle(chunk):
    """``T (I + A) = I`` at f32 rounding on packed chunks with two resets
    each and write strengths near 2 (``linear_allow_neg_eigval``), where
    ``A``'s entries are largest; ``P`` is the masked, decayed ``Q K^T``.
    ``A`` and ``P`` are formed here in float64 from their definitions.  The
    residual, over the inverse's largest entry, reads 1.4e-6 at chunk 64 and
    1.9e-7 at 16 (a 64-term f32 sum: 64 x 1.2e-7); one bf16 pass in the
    doubling would read 1e-3."""
    t = 2 * chunk
    q, k, _, g, _ = _scan_inputs(5, t)
    beta = jnp.full((H, t), 2.0 - 1e-3, jnp.float32)
    cuts = (0, chunk // 3, chunk - 5, chunk, chunk + 7, t - chunk // 4)
    lens = tuple(np.diff(cuts + (t,)))
    seg = _seg_start(lens)
    cols, g_rows, _ = gd._side_inputs(g, beta, jnp.asarray(seg), chunk)
    inv, qk = gd._prepass(jnp.full((1,), 2, jnp.int32), q, k, cols, g_rows,
                          chunk=chunk, interpret=True)
    assert inv.shape == qk.shape == (H, t, chunk)
    q64, k64, g64 = (np.asarray(a, np.float64) for a in (q, k, g))
    worst = 0.0
    for c in range(2):
        at = slice(c * chunk, (c + 1) * chunk)
        i, j = np.mgrid[:chunk, :chunk]
        same = seg[at][:, None] == seg[at][None, :]
        for h in range(H):
            big = np.cumsum(g64[h, at])
            decay = np.where(same & (j <= i), np.exp(big[:, None] - big), 0.0)
            a = np.where(j < i, float(beta[0, 0]) * decay
                         * (k64[h, at] @ k64[h, at].T), 0.0)
            got = np.asarray(inv[h, at], np.float64)
            assert np.array_equal(np.triu(got, 1), np.zeros_like(got))
            worst = max(worst, np.abs(got @ (np.eye(chunk) + a)
                                      - np.eye(chunk)).max()
                        / max(1.0, np.abs(got).max()))
            np.testing.assert_allclose(
                qk[h, at], decay * (q64[h, at] @ k64[h, at].T), atol=1e-6)
    assert worst < 1e-5, worst


def test_convolution_stops_at_a_rows_first_event():
    lens = (5, 1, 2, 9, 1, 1)
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(sum(lens), 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    seg = _seg_start(lens)
    positions = jnp.asarray(np.arange(len(seg)) - seg)
    got = gd.causal_conv(x, w, positions)
    want = jnp.concatenate([
        _conv(x[s:s + n], w) for s, n in zip(np.cumsum((0,) + lens[:-1]),
                                             lens)])
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the first three tokens of a row read 1, 2 and 3 taps
    np.testing.assert_allclose(got[8], x[8] * w[3], atol=1e-6)
    np.testing.assert_allclose(got[9], x[9] * w[3] + x[8] * w[2], atol=1e-6)
    np.testing.assert_allclose(
        got[10], x[10] * w[3] + x[9] * w[2] + x[8] * w[1], atol=1e-6)


# -- (c) the carry ---------------------------------------------------------------


@pytest.mark.parametrize("cut", [(7, 1, 20), (33, 16, 2)])
def test_scan_of_two_parts_equals_one_scan(cut):
    """Three rows, each split into A || B at ``cut[r]``: scan(B) from the
    state and the convolution tail that scan(A) returned equals the second
    part of scan(A || B), and B's final state the whole's."""
    whole = (40, 17, 39)
    t, chunk = 128, 16
    pad = t - sum(whole)
    args = _scan_inputs(9, t)
    lens = whole + (1,) * pad
    seg = jnp.asarray(_seg_start(lens))
    starts = np.cumsum((0,) + whole[:-1]).astype(np.int32)
    lasts = (starts + np.array(whole) - 1).astype(np.int32)
    full, full_state = gd.gdn_scan(
        *args, seg, chunk=chunk, row_start=jnp.asarray(starts),
        row_last=jnp.asarray(lasts), output_final_state=True, interpret=True)
    want, want_state = _one_by_one(args, whole)
    assert float(jnp.abs(full[:, :sum(whole)] - want).max()) < SCAN_TOL
    assert float(jnp.abs(full_state - want_state).max()) < STATE_TOL

    def packed(parts):
        """The named slices of every row end to end, padded to ``t``."""
        idx = np.concatenate([np.arange(a, b) for a, b in parts])
        n = len(idx)
        idx = np.concatenate([idx, np.zeros(t - n, np.int64)])
        lens = [b - a for a, b in parts] + [1] * (t - n)
        rs = np.cumsum([0] + [b - a for a, b in parts[:-1]]).astype(np.int32)
        rl = (rs + np.array([b - a for a, b in parts]) - 1).astype(np.int32)
        return ([a[:, idx] for a in args], jnp.asarray(_seg_start(lens)),
                jnp.asarray(rs), jnp.asarray(rl), idx[:n])

    a_args, a_seg, a_rs, a_rl, _ = packed(
        [(s, s + c) for s, c in zip(starts, cut)])
    _, state_a = gd.gdn_scan(*a_args, a_seg, chunk=chunk, row_start=a_rs,
                             row_last=a_rl, output_final_state=True,
                             interpret=True)
    b_args, b_seg, b_rs, b_rl, b_idx = packed(
        [(s + c, s + n) for s, c, n in zip(starts, cut, whole)])
    got_b, state_b = gd.gdn_scan(
        *b_args, b_seg, chunk=chunk, h0=state_a, row_start=b_rs,
        row_last=b_rl, output_final_state=True, interpret=True)
    assert float(jnp.abs(got_b[:, :len(b_idx)]
                         - full[:, b_idx]).max()) < SCAN_TOL
    assert float(jnp.abs(state_b - full_state).max()) < STATE_TOL
    # the convolution's part of the carry: the last three inputs of A
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(t, 5)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    pos = jnp.arange(t) - seg
    y = gd.causal_conv(x, w, pos)
    a_idx = np.concatenate([np.arange(s, s + c) for s, c in zip(starts, cut)])
    tail = gd.conv_tail(x[a_idx], a_rs, a_rl, 4)
    assert tail.shape == (3, 3, 5)
    row_of = np.concatenate([np.full(n - c, r_) for r_, (c, n) in
                             enumerate(zip(cut, whole))])
    pos_b = np.asarray(b_seg)[:len(b_idx)]
    y_b = gd.causal_conv(x[b_idx], w, jnp.arange(len(b_idx)) - pos_b,
                         tail=tail, row_of=jnp.asarray(row_of))
    np.testing.assert_allclose(y_b, y[b_idx], atol=1e-5)
    # and a tail of a tail: a one-token part keeps two inputs of the old
    one = gd.conv_tail(x[b_idx][:1], jnp.zeros(1, jnp.int32),
                       jnp.zeros(1, jnp.int32), 4, tail=tail[:1])
    np.testing.assert_allclose(one[0, :2], tail[0, 1:], atol=1e-6)
    np.testing.assert_allclose(one[0, 2], x[b_idx][0], atol=1e-6)


def test_packed_attention_is_causal_within_a_history():
    lens = (70, 1, 150, 35)
    t = sum(lens)
    r = np.random.default_rng(2)
    q, k, v = (jnp.asarray(r.normal(size=(3, t, 16)), jnp.float32)
               for _ in range(3))
    seg = _seg_start(lens)
    got = packed_causal_attention(q, k, v, jnp.asarray(seg), block=64,
                                  interpret=True)
    at = np.arange(t)
    mask = (at[None, :] <= at[:, None]) & (at[None, :] >= seg[:, None])
    s = jnp.einsum("htd,hsd->hts", q, k) / 4.0
    want = jnp.einsum("hts,hsd->htd", jax.nn.softmax(
        jnp.where(mask[None], s, -jnp.inf), -1), v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- (d) the model against its plain reference ----------------------------------


@pytest.fixture(scope="module")
def weights():
    bf = gh.init_params(CFG, 3_000_000_007)
    return {"bf16": bf,
            "f32": {k: v.astype(jnp.float32) for k, v in bf.items()}}


def _histories(seed, lens):
    r = np.random.default_rng(seed)
    return [r.integers(0, CFG.vocab_size, n).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def program():
    @jax.jit
    def run(P, flat):
        return gh.forward_flat(CFG, P, flat, 128, K,
                               score_backend="reference")
    return run


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_packed_program_meets_the_reference_on_f32_weights(weights, program):
    hists = _histories(1, (37, 1, 70, 5))
    out = program(weights["f32"], jnp.asarray(gh.flatten(
        gh.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        ref = reference_forward(CFG, weights["f32"], h)
        assert _rel(out["h_last"][r], ref["h_last"]) < F32_TOL
        logits = np.asarray(ref["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=F32_TOL * np.abs(logits).max())


def test_bf16_program_stays_within_rounding_and_the_control_does_not(
        weights, program):
    hists = _histories(2, (64, 9, 33))
    out = program(weights["bf16"], jnp.asarray(gh.flatten(
        gh.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        ref = reference_forward(CFG, weights["bf16"], h)
        assert _rel(out["h_last"][r], ref["h_last"]) < BF16_TOL
        logits = np.asarray(ref["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=BF16_TOL * np.abs(logits).max())
        # the control: a reference without the q/k normalisation is another
        # model, and the tolerance must tell
        wrong = reference_forward(CFG, weights["bf16"], h,
                                  normalize_qk=False)
        assert _rel(out["h_last"][r], wrong["h_last"]) > 2 * BF16_TOL


def test_config_reads_the_pattern_and_refuses_what_it_does_not_implement():
    assert CFG.period == ("linear_attention",) * 3 + ("full_attention",)
    assert CFG.n_periods == 2 and CFG.n_linear_layers == 6
    odd = gh.GDNHybridConfig.from_hf(dict(
        HF, num_hidden_layers=3, layer_types=["linear_attention",
                                              "full_attention",
                                              "full_attention"]))
    assert odd.n_periods == 1 and len(odd.period) == 3
    with pytest.raises(ValueError, match="rope_parameters"):
        gh.GDNHybridConfig.from_hf(
            {**HF, "rope_parameters": {"rope_theta": 500000}})
    with pytest.raises(ValueError, match="num_key_value_heads"):
        gh.GDNHybridConfig.from_hf({**HF, "num_key_value_heads": 2})
    with pytest.raises(ValueError, match="layer_types"):
        gh.GDNHybridConfig.from_hf({**HF, "num_hidden_layers": 7})


def test_published_cut_counts_the_parameters_the_issue_states():
    full = dict(HF, vocab_size=100352, hidden_size=3840,
                intermediate_size=11008, num_hidden_layers=16,
                num_attention_heads=30, num_key_value_heads=30,
                linear_num_key_heads=30, linear_num_value_heads=30,
                linear_key_head_dim=96, linear_value_head_dim=192,
                layer_types=HF["layer_types"] * 2)
    cfg = gh.GDNHybridConfig.from_hf(full)
    assert abs(cfg.param_count() - 4.10e9) < 0.01e9  # 8.20 GB in bf16
    shapes = gh.param_shapes(cfg)
    assert shapes["S0.qkv"][0] == (4, 3840, 11520)
    assert shapes["S3.qkv"][0] == (4, 3840, 11520)  # q | k | v, 3 x 3,840


# -- (e) ONE scorer class for both families -------------------------------------


def test_one_scorer_class_serves_both_families(weights):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K,
                              ladder=(64, 128), max_rows=4)
    assert sc.compile_count == 2 and sc.warmup_executions == 2
    hists = _histories(10, (5, 20, 17, 3, 60, 64, 20))  # 3 dispatches
    idx, vals = sc.score_topk(hists, 5)
    assert idx.shape == (7, 5) and sc.compile_count == 2
    for r, h in enumerate(hists):
        ref = np.asarray(reference_forward(CFG, weights["f32"], h)["logits"])
        np.testing.assert_allclose(vals[r], np.sort(ref)[::-1][:5],
                                   rtol=1e-4, atol=1e-5)
    st = sc.stats()
    assert st["family"] == "gdn_hybrid_sequence"
    assert st["calls"] == 3 and st["queries"] == 7 and st["tokens"] == 189
    assert st["tokens"] + st["padded_tokens"] == sum(
        int(t) * n for t, n in st["bucket_hits"].items())
    assert st["scan_tokens"] == 189 * 6 and st["scan_rows"] == 7 * 6
    assert st["scan_chunks"] == 6 * sum(
        int(t) // 64 * n for t, n in st["bucket_hits"].items())
    assert "experts_touched" not in st  # the MoE family's, not everyone's
    assert set(sc.forward(hists[:1])) >= {"values", "indices", "h_last",
                                          "batch"}
    # the other family through the SAME class keeps its own counters
    moe_cfg = lm.LatentMoEConfig.from_hf(dict(
        vocab_size=300, hidden_size=64, num_hidden_layers=2,
        intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16), max_len=64)
    moe = PackedSequenceScorer(moe_cfg, lm.init_params(moe_cfg, 5), max_k=K,
                               ladder=(64,), max_rows=4)
    assert type(moe) is type(sc)
    moe.score_topk(hists[:2], 3)
    st = moe.stats()
    assert st["family"] == "latent_moe_sequence" and st["compile_count"] == 1
    assert st["expert_assignments"] == 25 * 2 and "scan_tokens" not in st


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
    app_id = storage.get_meta_data_apps().insert(App(0, "gdnapp"))
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
        "datasource": {"params": {"appName": "gdnapp"}},
        "algorithms": [{"name": "gdnhybrid", "params": {
            "appName": "gdnapp", "modelConfig": hf, "maxLen": 16, "seed": 5,
            "tokenLadder": [64, 128], "maxRows": 4, "maxK": 8}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "gdn", storage=storage, ctx=ctx)
    qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
    yield qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
    qs.stop()
    store_mod.set_storage(None)


def test_template_serves_gdnhybrid_through_the_batcher(served):
    from predictionio_tpu.templates.sequentialrecommendation import (
        EventStoreHistory,
    )

    qs, base = served
    assert _http(base + "/readyz")["fastpathWarm"] is True
    fp = _http(base + "/")["fastpath"][0]
    assert fp["family"] == "gdn_hybrid_sequence"
    assert fp["compile_count"] == 2 and fp["calls"] == 0
    assert qs._batcher.buckets == (1, 2, 3, 4, 64)
    model = qs._deployed.models[0]
    for u, num in ((0, 3), (5, 8), (3, 4)):
        ans = _http(base + "/queries.json", {"user": f"u{u}", "num": num})
        scores = [s["score"] for s in ans["itemScores"]]
        assert len(scores) == num and scores == sorted(scores, reverse=True)
        hist = EventStoreHistory("gdnapp", ("view", "buy", "rate")
                                 ).recent_indices(f"u{u}", 16, model.item_map)
        got = [model.item_map[s["item"]] for s in ans["itemScores"]]
        want = np.asarray(reference_forward(
            model.config, model.params, hist)["logits"], np.float64)
        np.testing.assert_allclose(scores, want[got],
                                   atol=BF16_TOL * np.abs(want).max())
    assert _http(base + "/queries.json",
                 {"user": "nobody", "num": 3}) == {"itemScores": []}
    after = _http(base + "/")["fastpath"][0]
    assert after["compile_count"] == 2 and after["calls"] == 3
    assert after["scan_rows"] == 3 * 6
    recs = _http(base + "/trace/dispatches.json")["dispatches"]
    assert recs[-1]["rung"] in (64, 128)


def test_train_refuses_a_published_width_and_shares_the_algorithm():
    from predictionio_tpu.templates import sequentialrecommendation as t

    assert t.GDNHybridAlgorithm.batch_predict is \
        t.LatentMoEAlgorithm.batch_predict
    assert t.GDNHybridAlgorithm.warmup is t.PackedSequenceAlgorithm.warmup
    algo = t.GDNHybridAlgorithm(t.PackedSequenceParams(modelConfig=dict(
        HF, hidden_size=3840, intermediate_size=11008, vocab_size=100352)))
    pd = type("PD", (), {"interactions": type("I", (), {
        "n_items": 100, "item_map": None})(), "histories": None})()
    with pytest.raises(NotImplementedError, match="no trainer"):
        algo.train(None, pd)
    model = dataclasses.make_dataclass("M", ["histories"])(object())
    assert algo._histories(model) is model.histories
