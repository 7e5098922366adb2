"""The state-space / attention family with routed experts behind every layer
at a small size on the CPU: the softmax-over-the-picks router against a
written-out one, the scan at this family's shapes (ONE group of many narrow
heads) against the token-by-token recurrence, the held experts through the
run's one table of groups, the shares of one layer over its ranks, the
packed serving program against the plain reference, every multiplier, the
layer pattern, and the family through the ONE scorer class and the template.

Tolerances, and why each:

* ``SCAN_TOL`` 2e-5 (relative to the outputs' largest): on f32 inputs the
  chunked form computes the recurrence's f32 sums in another order;
  readings are 1e-7 - 1e-6.  ``STATE_TOL`` 2e-5 of states of size ~3.
* ``F32_TOL`` 5e-5 (relative L2 of ``x_last`` / ``h_last``, and of the
  logits' largest): on f32 weights the program and the reference compute
  the same sums in another order; four layers read 3-8e-7.
* ``BF16_TOL`` 0.03 (``added_rel_err``: the error of the f32 residual
  stream at the last position over the norm of what the LAYERS ADDED to
  it): bf16 operands round to 3 significant digits; four pre-normed layers
  at hidden 64 read 0.004-0.012 with the routing as the program's own
  rounding leaves it.  Changing ANY ONE multiplier, the attention's scale,
  the no-rotary rule or the tied head reads 0.05 or more (``MOVED_TOL``).
* ``SHARE_TOL`` 2e-6 (of the layer's largest output): the ranks' parts are
  f32 sums of the same products in another order; readings are 1-3e-7.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import ssm_moe as sm
from predictionio_tpu.models import ssm_moe_reference as ref
from predictionio_tpu.models import ssm_parallel_reference as ssd_ref
from predictionio_tpu.ops import moe
from predictionio_tpu.ops import ssd_scan as ssd

SCAN_TOL, STATE_TOL, F32_TOL, BF16_TOL, MOVED_TOL, SHARE_TOL = (
    2e-5, 2e-5, 5e-5, 0.03, 0.05, 2e-6)
M, A = sm.MAMBA, sm.ATTENTION

# the published multipliers and small widths: rank 1 of 2 holds experts 4-7
HF = dict(
    vocab_size=300, hidden_size=64, num_hidden_layers=4, intermediate_size=32,
    shared_intermediate_size=48, num_local_experts=8, num_experts_per_tok=3,
    num_attention_heads=4, num_key_value_heads=2, layer_types=[M, M, A, M],
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=32,
    mamba_d_conv=4, mamba_chunk_size=16, mamba_expand=2,
    attention_multiplier=0.0078125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
    hidden_act="silu", attention_bias=False, mamba_conv_bias=True,
    mamba_proj_bias=False, normalization_function="rmsnorm",
    position_embedding_type="nope", rope_scaling=None,
    tie_word_embeddings=True, num_experts_held=4, first_expert_held=4,
)
CFG = sm.SSMMoEConfig.from_hf(HF, max_len=64)
K = 10


# -- (a) the router ---------------------------------------------------------------


def test_router_picks_the_largest_logits_and_softmaxes_over_them_alone():
    r = np.random.default_rng(0)
    x = r.normal(size=(40, 16)).astype(np.float32)
    w = r.normal(size=(16, 9)).astype(np.float32)
    w[:, 5] = w[:, 2]  # experts 2 and 5 tie on every token
    picked, weights, logits = moe.route_topk_softmax(
        jnp.asarray(x), jnp.asarray(w), top_k=4)
    want_logits = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=1e-5)
    lg = np.asarray(logits, np.float64)
    for t in range(40):
        # written out: a stable sort by descending logit, ties to the lower
        # index; the weights exp(l - max) over the PICKED four alone
        order = sorted(range(9), key=lambda e: (-lg[t, e], e))[:4]
        assert list(np.asarray(picked[t])) == order
        e = np.exp(lg[t, order] - lg[t, order].max())
        np.testing.assert_allclose(weights[t], e / e.sum(), rtol=1e-5)
    assert picked.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               rtol=1e-6)
    tied = [t for t in range(40) if 2 in picked[t] or 5 in picked[t]]
    assert tied and all(
        list(np.asarray(picked[t])).index(2) + 1
        == list(np.asarray(picked[t])).index(5)
        for t in tied if 5 in picked[t])
    # a softmax over ALL the logits weighs the picks differently
    full = jax.nn.softmax(logits, axis=1)
    assert float(jnp.abs(
        jnp.take_along_axis(full, picked, 1) - weights).max()) > 0.05


# -- (b) the scan at this family's shapes ----------------------------------------

H, P, N = 32, 64, 32  # ONE group of 32 heads of width 64: two grid steps


def _scan_inputs(seed, t):
    r = np.random.default_rng(seed)
    x = r.normal(size=(t, H * P))
    b, c = 0.3 * r.normal(size=(2, t, N))
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.5), size=(t, H)))
    a = -r.uniform(1, 16, size=H)
    d = r.normal(size=H)
    return [jnp.asarray(v, jnp.float32) for v in (x, b, c, dt, a, d)]


def _seg_start(lens):
    starts = np.cumsum([0] + list(lens[:-1]))
    return np.concatenate(
        [np.full(n, s) for n, s in zip(lens, starts)]).astype(np.int32)


def _recurrence(args, at, n, h0=None):
    x, b, c, dt, a, d = args
    y, h = ssd_ref.ssd_recurrence(
        x[at:at + n].reshape(n, H, P), b[at:at + n].reshape(n, 1, N),
        c[at:at + n].reshape(n, 1, N), dt[at:at + n], a, d, h0)
    return y.reshape(n, H * P), h


def _one_by_one(args, lens):
    outs, finals, at = [], [], 0
    for n in lens:
        y, h = _recurrence(args, at, n)
        outs.append(y)
        finals.append(h)
        at += n
    return jnp.concatenate(outs), jnp.stack(finals)


def _scan(args, seg, **kw):
    return ssd.ssd_scan(*args, jnp.asarray(seg), n_groups=1, interpret=True,
                        **kw)


def test_one_group_of_narrow_heads_resets_in_mid_chunk_and_skips_the_tail():
    # rows start in mid-chunk (chunks of 32), one is a single token, and
    # two whole chunks of padding (one-token histories) follow
    real = (40, 1, 23, 30)
    t = 160
    lens = real + (1,) * (t - sum(real))
    args = _scan_inputs(21, t)
    want, _ = _one_by_one(args, real)
    got = _scan(args, _seg_start(lens), chunk=32, n_real=jnp.int32(sum(real)))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got[:sum(real)] - want).max()) < SCAN_TOL * scale
    assert not bool(got[96:].any())  # the chunks past the last real token
    assert ssd.scan_chunks(t, 32, n_real=sum(real)) == 3
    # every head reads the ONE group's B and C: with C negated all flip
    x, b, c, dt, a, d = args
    other = _scan([x, b, -c, dt, a, d], _seg_start(lens), chunk=32)
    skip = jnp.repeat(d, P)[None, :] * x
    np.testing.assert_allclose(
        (other - skip)[:94], -(got - skip)[:94], atol=SCAN_TOL * scale)


def test_scan_of_two_parts_equals_one_scan_at_one_group():
    """Two rows, each split into A || B: scan(B) from the state scan(A)
    returned equals the second part of scan(A || B)."""
    whole, cut = (50, 41), (19, 32)
    t, chunk = 96, 32
    args = _scan_inputs(22, t)
    lens = whole + (1,) * (t - sum(whole))
    starts = np.cumsum((0,) + whole[:-1]).astype(np.int32)
    lasts = (starts + np.array(whole) - 1).astype(np.int32)
    full, full_state = _scan(
        args, _seg_start(lens), chunk=chunk, row_start=jnp.asarray(starts),
        row_last=jnp.asarray(lasts), output_final_state=True)
    want, want_state = _one_by_one(args, whole)
    scale = float(jnp.abs(want).max())
    assert full_state.shape == (2, H, P, N)
    assert float(jnp.abs(full[:sum(whole)] - want).max()) < SCAN_TOL * scale
    assert float(jnp.abs(full_state - want_state).max()) < STATE_TOL * 3

    def packed(parts):
        idx = np.concatenate([np.arange(a, b) for a, b in parts])
        n = len(idx)
        idx = np.concatenate([idx, np.zeros(t - n, np.int64)])
        lens = [b - a for a, b in parts] + [1] * (t - n)
        rs = np.cumsum([0] + [b - a for a, b in parts[:-1]]).astype(np.int32)
        rl = (rs + np.array([b - a for a, b in parts]) - 1).astype(np.int32)
        return ([a[idx] for a in args[:4]] + args[4:], _seg_start(lens),
                jnp.asarray(rs), jnp.asarray(rl), idx[:n])

    a_args, a_seg, a_rs, a_rl, _ = packed(
        [(s, s + c) for s, c in zip(starts, cut)])
    _, state_a = _scan(a_args, a_seg, chunk=chunk, row_start=a_rs,
                       row_last=a_rl, output_final_state=True)
    b_args, b_seg, b_rs, b_rl, b_idx = packed(
        [(s + c, s + n) for s, c, n in zip(starts, cut, whole)])
    got_b, state_b = _scan(
        b_args, b_seg, chunk=chunk, h0=state_a, row_start=b_rs,
        row_last=b_rl, output_final_state=True)
    assert float(jnp.abs(got_b[:len(b_idx)] - full[b_idx]).max()) \
        < SCAN_TOL * scale
    assert float(jnp.abs(state_b - full_state).max()) < STATE_TOL * 3


# -- (c) the held experts through the run's one table -----------------------------


@pytest.fixture(scope="module")
def weights():
    bf = sm.init_params(CFG, 3_000_000_011)
    return {"bf16": bf,
            "f32": {k: v.astype(jnp.float32) for k, v in bf.items()}}


def _uncut(seed=3_000_000_011):
    """The same model with every expert held (the seeded tensors are drawn
    by name and shape, so only the experts' stacks differ in shape)."""
    cfg = dataclasses.replace(CFG, num_experts_held=None, first_expert_held=0)
    return cfg, {k: v.astype(jnp.float32)
                 for k, v in sm.init_params(cfg, seed).items()}


def _share(P, first, held):
    """The parameters a rank that holds experts ``[first, first + held)``
    has: everything, and its slice of every run's experts."""
    return {k: (v[:, first:first + held]
                if k.rpartition(".")[2] in sm.EXPERT_TABLES else v)
            for k, v in P.items()}


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """THE SHARES TEST.  Two ranks hold experts 0-3 and 4-7 of one layer:
    the routed parts they give, with the mixer and the shared expert (which
    every rank computes alike) counted once, add up to what the uncut
    reference gives for the whole layer — in the reference and in the
    program, for a mamba layer (layer 1 of run 0) and the attention layer."""
    full_cfg, P = _uncut()
    r = np.random.default_rng(5)
    h = r.integers(0, 300, 48).astype(np.int32)
    b = sm.pack([h], 64, 1)
    pos, seg, valid = (jnp.asarray(b[k]) for k in (
        "positions", "seg_start", "valid"))
    x = full_cfg.embedding_multiplier * P["head"][b["tokens"]]
    for i in (1, 2):
        kind, W = ref.layer_weights(full_cfg, P, i)
        want, (m, routed, shared) = ref.layer(full_cfg, kind, W, x[:48])
        rm = full_cfg.residual_multiplier
        scale = float(jnp.abs(want).max())
        parts_ref, parts_prog = [], []
        for first in (0, 4):
            cfg = dataclasses.replace(
                full_cfg, num_experts_held=4, first_expert_held=first)
            Ps = _share(P, first, 4)
            _, Ws = ref.layer_weights(cfg, Ps, i)
            _, (m_r, routed_r, shared_r) = ref.layer(cfg, kind, Ws, x[:48])
            np.testing.assert_allclose(m_r, m, atol=SHARE_TOL * scale)
            np.testing.assert_allclose(shared_r, shared,
                                       atol=SHARE_TOL * scale)
            parts_ref.append(routed_r)
            j, at = (0, i) if i < 2 else (1, 0)
            Wj, tables = sm.run_weights(Ps, j)
            n = tables[0].shape[0] // 4
            _, (m_p, routed_p, shared_p), (_, counts, unheld) = sm.layer(
                cfg, kind, {k: v[at] for k, v in Wj.items()}, tables,
                jnp.int32(at), n, x, pos, seg, valid, interpret=True)
            assert _rel(m_p[:48], m) < F32_TOL
            assert _rel(shared_p[:48], shared) < F32_TOL
            assert not bool(routed_p[48:].any())  # padded tokens: nothing
            parts_prog.append(routed_p[:48])
            assert 0 < int(counts.sum()) < 48 * 3
            assert int(unheld) < 48
        for parts in (parts_ref, parts_prog):
            np.testing.assert_allclose(
                parts[0] + parts[1], routed, atol=20 * SHARE_TOL * float(
                    jnp.abs(routed).max()))
            whole = x[:48] + rm * m
            whole = whole + rm * (parts[0] + parts[1] + shared)
            np.testing.assert_allclose(whole, want, atol=20 * SHARE_TOL * scale)
        # each rank's part is a visible share of the layer's routed output
        for part in parts_ref:
            assert float(jnp.linalg.norm(part)) > 0.2 * float(
                jnp.linalg.norm(routed))


def test_no_token_is_dropped_whatever_the_skew_and_padding_touches_nothing(
        weights):
    """Every token picks the SAME three held experts (a router whose
    logits do not depend on the token): every assignment is computed, and
    the padded tokens' rows are zero."""
    P = dict(weights["f32"])
    W, tables = sm.run_weights(P, 0)
    Wi = {k: v[0] for k, v in W.items()}
    gate = np.zeros((64, 8), np.float32)
    Wi["gate"] = jnp.asarray(gate)  # all logits 0: ties -> experts 0, 1, 2
    Wi["gate"] = Wi["gate"].at[:, 5].set(1e-3).at[:, 6].set(2e-3)
    r = np.random.default_rng(6)
    x = jnp.asarray(r.normal(size=(64, 64)), jnp.float32) + 1.0
    valid = jnp.arange(64) < 50
    routed, shared, picked, counts, unheld = sm.feed_forward(
        CFG, Wi, tables, jnp.int32(0), 2, x, valid, True)
    f = ref._rms(x, Wi["ffn_norm"], 1e-5)
    pk, wt, _ = ref.route(CFG, Wi, f)
    np.testing.assert_array_equal(np.sort(picked, 1), np.sort(pk, 1))
    kind, Wref = ref.layer_weights(CFG, P, 0)
    want = ref.routed_experts(CFG, {**Wref, "gate": Wi["gate"]}, f, pk, wt)
    assert _rel(routed[:50], want[:50]) < F32_TOL
    assert not bool(routed[50:].any())
    # experts 5 and 6 (local 1, 2) are held here and every valid token
    # picked them; the third pick (expert 0, by the tie) is held elsewhere
    assert list(np.asarray(counts)) == [0, 50, 50, 0]
    assert int(unheld) == 0


# -- (d) the model against its plain reference ------------------------------------


def _histories(seed, lens):
    r = np.random.default_rng(seed)
    return [r.integers(0, CFG.vocab_size, n).astype(np.int32) for n in lens]


_WANT = {}


def _want(weights, h):
    key = h.tobytes()
    if key not in _WANT:
        _WANT[key] = ref.reference_forward(CFG, weights["f32"], h)
    return _WANT[key]


def _program(cfg, t=128):
    @jax.jit
    def run(P, flat):
        return sm.forward_flat(cfg, P, flat, t, K, score_backend="reference")
    return run


@pytest.fixture(scope="module")
def program():
    return _program(CFG)


def _rel(got, want, over=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    over = want if over is None else np.asarray(over, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(over))


def _added_rel_err(P32, out, r, h, want):
    """The program's f32 residual stream of row ``r`` against the
    reference's, over what the layers added to the embedding."""
    x0 = CFG.embedding_multiplier * np.asarray(P32["head"])[h[-1]]
    return _rel(out["x_last"][r], want["x_last"],
                np.asarray(want["x_last"]) - x0)


def test_packed_program_meets_the_reference_on_f32_weights(weights, program):
    hists = _histories(1, (37, 1, 70, 5))
    out = program(weights["f32"], jnp.asarray(sm.flatten(
        sm.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        want = _want(weights, h)
        assert _rel(out["h_last"][r], want["h_last"]) < F32_TOL
        assert _rel(out["x_last"][r], want["x_last"]) < F32_TOL
        logits = np.asarray(want["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=F32_TOL * np.abs(logits).max())
    assert out["picks"].shape == (4, 128, 3)
    assert out["expert_counts"].shape == (4, 4)
    # half the router's experts are held: about half the assignments
    share = int(out["expert_counts"].sum()) / (4 * 113 * 3)
    assert 0.3 < share < 0.7


def test_packed_rows_equal_the_rows_alone(weights, program):
    hists = _histories(5, (50, 3, 40, 17))
    packed = program(weights["f32"], jnp.asarray(sm.flatten(
        sm.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        alone = program(weights["f32"], jnp.asarray(sm.flatten(
            sm.pack([h], 128, 8))))
        assert _rel(packed["x_last"][r], alone["x_last"][0]) < F32_TOL


def test_bf16_program_stays_within_rounding(weights, program):
    hists = _histories(2, (64, 9, 33))
    out = program(weights["bf16"], jnp.asarray(sm.flatten(
        sm.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        want = _want(weights, h)
        assert _added_rel_err(weights["f32"], out, r, h, want) < BF16_TOL
        assert _rel(out["h_last"][r], want["h_last"]) < BF16_TOL


def _attention_with_rotary(cfg, W, a):
    """The reference's attention mixer with a half-rotation rotary
    embedding on q and k: what this family must NOT do."""
    t = a.shape[0]
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // hq
    qkv = a @ W["qkv"]
    q = ssd_ref._rope_half(qkv[:, :hq * hd].reshape(t, hq, hd), 10000.0)
    k = ssd_ref._rope_half(
        qkv[:, hq * hd:(hq + hkv) * hd].reshape(t, hkv, hd), 10000.0)
    rotated = jnp.concatenate(
        [q.reshape(t, -1), k.reshape(t, -1), qkv[:, (hq + hkv) * hd:]], 1)
    eye = jnp.eye(rotated.shape[1], dtype=jnp.float32)
    return _PLAIN_ATTENTION(cfg, {**W, "qkv": eye}, rotated)


_PLAIN_ATTENTION = ref.attention_mixer


CHANGES = {
    "embedding_multiplier": dict(embedding_multiplier=1.0),
    "residual_multiplier": dict(residual_multiplier=1.0),
    "attention_multiplier": dict(attention_multiplier=1.0),
    "attention_scale_1_over_sqrt_head": dict(attention_multiplier=0.25),
    "logits_scaling": dict(logits_scaling=1.0),
    "rotary_on_the_attention_layer": "rotary",
    "untied_head": "untied",
}


@pytest.mark.parametrize("name", CHANGES)
def test_changing_any_one_rule_moves_the_output(weights, name, monkeypatch):
    """ONE multiplier of the config set to 1, the attention's scale taken as
    ``1 / sqrt(head size)``, a rotary embedding on the attention layer, or a
    head that is not the embedding: each reads past the tolerance the sound
    bf16 program stays inside.  The multipliers are changed in the PROGRAM
    and held against the reference that applies them all; rotary and the
    untied head are the REFERENCE computed that way against the sound
    program."""
    change = CHANGES[name]
    hists = _histories(3, (48, 21))
    flat = jnp.asarray(sm.flatten(sm.pack(hists, 128, 8)))
    cfg = (dataclasses.replace(CFG, **change) if isinstance(change, dict)
           else CFG)
    out = _program(cfg)(weights["bf16"], flat)
    P32 = dict(weights["f32"])
    if change == "rotary":
        monkeypatch.setattr(
            ref, "attention_mixer", _attention_with_rotary)
    if change == "untied":
        other = sm.init_params(CFG, 99)["head"].astype(jnp.float32)
    worst = 0.0
    for r, h in enumerate(hists):
        want = (_want(weights, h) if isinstance(change, dict)
                else ref.reference_forward(CFG, P32, h))
        if change == "untied":
            logits = np.asarray(other[:CFG.vocab_size] @ want["h_last"])
            worst = max(worst, float(np.abs(
                np.asarray(out["values"][r]) - np.sort(logits)[::-1][:K]
            ).max() / np.abs(logits).max()))
            continue
        worst = max(worst,
                    _added_rel_err(P32, out, r, h, want),
                    _rel(out["h_last"][r], want["h_last"]))
    assert worst > MOVED_TOL, (name, worst)


def test_each_part_of_a_layer_is_a_visible_part_of_the_stream(weights):
    """The seeded gains' purpose: through ``embedding_multiplier`` and
    ``residual_multiplier`` the mixer, the routed experts and the shared
    expert each add a norm of the same order."""
    P32 = weights["f32"]
    h = _histories(4, (40,))[0]
    x = CFG.embedding_multiplier * P32["head"][h]
    for i in (0, 2):
        kind, W = ref.layer_weights(CFG, P32, i)
        _, parts = ref.layer(CFG, kind, W, x)
        for part in parts:
            share = CFG.residual_multiplier * float(
                jnp.linalg.norm(part) / jnp.linalg.norm(x))
            assert 0.03 < share < 1.0, (i, share)


# -- (e) the layer pattern ---------------------------------------------------------


def test_the_published_pattern_scanned_as_runs_equals_the_layers_one_by_one():
    """``layer_types`` with attention at 5 of ten: the trunk scans runs of
    5 + 1 + 4 layers over stacked weights; the same ten layers called one by
    one, each on its own tensors, give the same stream."""
    kinds = [M] * 5 + [A] + [M] * 4
    cfg = sm.SSMMoEConfig.from_hf(dict(
        HF, hidden_size=32, num_hidden_layers=10, layer_types=kinds,
        mamba_n_heads=4, intermediate_size=16, shared_intermediate_size=16,
        num_attention_heads=2, num_key_value_heads=1), max_len=32)
    assert cfg.runs == ((M, 5), (A, 1), (M, 4))
    assert cfg.n_mamba_layers == 9
    P = {k: v.astype(jnp.float32) for k, v in sm.init_params(cfg, 8).items()}
    assert P["R0.ssm_in"].shape[0] == 5 and P["R2.e_w2"].shape[:2] == (4, 4)
    assert "R1.qkv" in P and "R1.ssm_in" not in P
    h = np.random.default_rng(8).integers(0, 300, 20).astype(np.int32)
    b = sm.pack([h], 32, 1)
    pos, seg, valid = (jnp.asarray(b[k]) for k in (
        "positions", "seg_start", "valid"))
    got, picks, counts, unheld = sm.trunk(
        cfg, P, jnp.asarray(b["tokens"]), pos, seg, valid, interpret=True)
    assert picks.shape == (10, 32, 3) and counts.shape == (10, 4)
    x = cfg.embedding_multiplier * P["head"][b["tokens"]]
    want_counts = []
    for i in range(10):
        kind, W = ref.layer_weights(cfg, P, i)
        tables = tuple(W.pop(k) for k in sm.EXPERT_TABLES)
        x, _, (_, c, _) = sm.layer(cfg, kind, W, tables, jnp.int32(0), 1, x,
                                   pos, seg, valid, interpret=True)
        want_counts.append(c)
    assert _rel(got[:20], x[:20]) < F32_TOL
    np.testing.assert_array_equal(counts, jnp.stack(want_counts))
    want = ref.reference_forward(cfg, P, h)
    assert _rel(got[19], want["x_last"]) < F32_TOL


# -- (f) the config and the counters ------------------------------------------------


def test_config_reads_the_published_keys_and_refuses_what_it_lacks():
    assert CFG.head_dim == 16 and CFG.mamba_d_ssm == 128
    assert CFG.conv_width == 128 + 2 * 32 and CFG.ssm_in_width == 128 + 192 + 8
    assert CFG.n_held == 4 and CFG.first_expert_held == 4
    assert CFG.runs == ((M, 2), (A, 1), (M, 1))
    for key, bad in (("mamba_conv_bias", False),
                     ("position_embedding_type", "rope"),
                     ("tie_word_embeddings", False),
                     ("normalization_function", "layernorm"),
                     ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            sm.SSMMoEConfig.from_hf({**HF, key: bad})
    with pytest.raises(ValueError, match="layer_types"):
        sm.SSMMoEConfig.from_hf({**HF, "layer_types": [M, A]})
    with pytest.raises(ValueError, match="mamba_expand"):
        sm.SSMMoEConfig.from_hf({**HF, "mamba_n_heads": 6})
    with pytest.raises(ValueError, match="not among"):
        sm.SSMMoEConfig.from_hf({**HF, "first_expert_held": 6})


def test_published_cut_counts_the_parameters_the_issue_states():
    kinds = ([M] * 5 + [A] + [M] * 9) * 2 + [M] * 5 + [A] + [M] * 4
    full = dict(HF, vocab_size=100352, hidden_size=4096,
                num_hidden_layers=10, layer_types=kinds[:10],
                intermediate_size=768, shared_intermediate_size=1536,
                num_local_experts=72, num_experts_per_tok=10,
                num_attention_heads=32, num_key_value_heads=8,
                mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
                mamba_chunk_size=256, num_experts_held=36,
                first_expert_held=0)
    cfg = sm.SSMMoEConfig.from_hf(full)
    assert abs(cfg.mixer_param_count(M) - 102.29e6) < 0.01e6
    assert abs(cfg.mixer_param_count(A) - 41.94e6) < 0.01e6
    assert abs(cfg.ffn_param_count() - 358.9e6) < 0.05e6
    assert abs(cfg.param_count() - 4962.7e6) < 0.05e6  # 9.93 GB in bf16
    shapes = sm.param_shapes(cfg)
    assert shapes["head"][0] == (100352, 4096) and "embed" not in shapes
    assert shapes["R0.ssm_in"][0] == (5, 4096, 16768)  # z | x | B | C | dt
    assert shapes["R0.conv"][0] == (5, 4, 8448)
    assert shapes["R1.qkv"][0] == (1, 4096, 6144)
    assert shapes["R2.e_w1"][0] == (4, 36, 4096, 768)
    assert shapes["R0.gate"] == ((5, 4096, 72), jnp.float32)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) \
        == cfg.param_count()
    whole = sm.SSMMoEConfig.from_hf(dict(full, num_experts_held=None))
    assert abs(whole.ffn_param_count() - 698.6e6) < 0.05e6
    # one pass gathers every assignment at two ranks: twice the even share
    assert moe.local_row_bound(8192 * 10, 36, 72) == 81920


def test_counters_count_the_scan_and_the_work_items():
    own = sm.DispatchCounters(CFG)  # chunks of 16; three mamba layers of 4
    counts = np.array([[5, 0, 130, 2], [0, 0, 0, 0], [100, 60, 1, 0],
                       [3, 3, 3, 3]])
    own.add(64, 2, 50, {"expert_counts": counts,
                        "tokens_unheld": np.array([4, 50, 0, 9])})
    st = own.stats()
    assert st["scan_layers"] == 3 and st["attention_layers"] == 1
    assert st["scan_chunks"] == 3 * 4 and st["scan_tokens"] == 150
    assert st["scan_rows"] == 6 and st["scan_chunk"] == 16
    assert st["experts_touched"] == 3 + 0 + 3 + 4
    assert st["expert_assignments"] == int(counts.sum())
    assert st["sparse_layer_dispatches"] == 3 and st["sparse_layers"] == 4
    assert st["routed_assignments"] == 4 * 50 * 3
    assert st["tokens_without_held_expert"] == 63
    assert st["experts_held"] == 4 and st["first_expert_held"] == 4
    # 64 x 3 = 192 rows a pass, not a multiple of 128: ONE row tile, so a
    # work item a touched expert
    assert st["expert_row_tiles"] == 10
    # tiles of 128 rows: rows [0, 5) [5, 135) [135, 137) -> 1 + 2 + 1 ...
    assert sm.row_tiles(counts, 128) == (1 + 2 + 1) + 0 + (1 + 2 + 1) + 4
    assert sm.row_tiles(np.array([0, 256, 0, 1]), 128) == 2 + 1


# -- (g) the ONE scorer class, the batcher and the query server ---------------------


def test_the_one_scorer_class_serves_this_family(weights):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K,
                              ladder=(64, 128), max_rows=4)
    assert sc.compile_count == 2 and sc.warmup_executions == 2
    hists = _histories(10, (5, 20, 17, 3, 60, 64, 20))  # 3 dispatches
    idx, vals = sc.score_topk(hists, 5)
    assert idx.shape == (7, 5) and sc.compile_count == 2
    for r in (0, 4, 6):  # a row of each of the three dispatches
        want = np.asarray(_want(weights, hists[r])["logits"])
        np.testing.assert_allclose(vals[r], np.sort(want)[::-1][:5],
                                   rtol=1e-4, atol=1e-6)
    st = sc.stats()
    assert st["family"] == "ssm_moe_sequence"
    assert st["calls"] == 3 and st["queries"] == 7 and st["tokens"] == 189
    assert st["scan_tokens"] == 189 * 3 and st["scan_rows"] == 7 * 3
    assert st["routed_assignments"] == 4 * 189 * 3
    assert 0 < st["expert_assignments"] < st["routed_assignments"]
    assert 0 < st["expert_row_tiles"] <= st["experts_touched"] * 2
    assert st["local_row_overflows"] == 0
    assert st["resident_bytes"] == sum(
        int(np.prod(v.shape)) * 4 for v in weights["f32"].values())
    assert "window_pairs" not in st and "linear_layers" not in st
    assert set(sc.forward(hists[:1])) >= {"values", "indices", "h_last",
                                          "x_last", "picks", "batch"}


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
    app_id = storage.get_meta_data_apps().insert(App(0, "smoeapp"))
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
        "datasource": {"params": {"appName": "smoeapp"}},
        "algorithms": [{"name": "ssmmoe", "params": {
            "appName": "smoeapp", "modelConfig": hf, "maxLen": 16, "seed": 5,
            "tokenLadder": [64, 128], "maxRows": 4, "maxK": 8}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "smoe", storage=storage, ctx=ctx)
    qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
    yield qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
    qs.stop()
    store_mod.set_storage(None)


def test_template_serves_ssmmoe_through_the_batcher(served):
    from predictionio_tpu.templates.sequentialrecommendation import (
        EventStoreHistory,
    )

    qs, base = served
    assert _http(base + "/readyz")["fastpathWarm"] is True
    fp = _http(base + "/")["fastpath"][0]
    assert fp["family"] == "ssm_moe_sequence"
    assert fp["compile_count"] == 2 and fp["calls"] == 0
    model = qs._deployed.models[0]
    P32 = {k: jnp.asarray(v, jnp.float32) for k, v in model.params.items()}
    for u, num in ((0, 3), (5, 8), (3, 4)):
        ans = _http(base + "/queries.json", {"user": f"u{u}", "num": num})
        scores = [s["score"] for s in ans["itemScores"]]
        assert len(scores) == num and scores == sorted(scores, reverse=True)
        hist = EventStoreHistory("smoeapp", ("view", "buy", "rate")
                                 ).recent_indices(f"u{u}", 16, model.item_map)
        got = [model.item_map[s["item"]] for s in ans["itemScores"]]
        want = np.asarray(ref.reference_forward(
            model.config, P32, hist)["logits"], np.float64)
        np.testing.assert_allclose(scores, want[got],
                                   atol=BF16_TOL * np.abs(want).max())
    assert _http(base + "/queries.json",
                 {"user": "nobody", "num": 3}) == {"itemScores": []}
    after = _http(base + "/")["fastpath"][0]
    assert after["compile_count"] == 2 and after["calls"] == 3
    assert after["scan_rows"] == 3 * 3 and after["scan_chunk"] == 16
    assert after["experts_held"] == 4 and after["expert_row_tiles"] > 0
    recs = _http(base + "/trace/dispatches.json")["dispatches"]
    assert recs[-1]["rung"] in (64, 128)


def test_train_refuses_a_published_width_and_shares_the_algorithm():
    from predictionio_tpu.templates import sequentialrecommendation as t

    assert t.SSMMoEAlgorithm.batch_predict is \
        t.LatentMoEAlgorithm.batch_predict
    assert t.SSMMoEAlgorithm.warmup is t.PackedSequenceAlgorithm.warmup
    algo = t.SSMMoEAlgorithm(t.PackedSequenceParams(modelConfig=dict(
        HF, hidden_size=4096, mamba_n_heads=128, mamba_d_head=64,
        intermediate_size=768, vocab_size=100352)))
    pd = type("PD", (), {"interactions": type("I", (), {
        "n_items": 100, "item_map": None})(), "histories": None})()
    with pytest.raises(NotImplementedError, match="no trainer"):
        algo.train(None, pd)
