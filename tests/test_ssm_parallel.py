"""The parallel state-space / attention family at a small size on the CPU:
the chunked scan against the token-by-token recurrence, packing, the state
carry, every multiplier, the block's sum of its two mixers, the packed
serving program against the plain reference, and the family through the ONE
scorer class and the template.

Tolerances, and why each:

* ``SCAN_TOL`` 2e-5 (relative to the outputs' largest, ~3-5): on f32 inputs
  the chunked form computes the recurrence's f32 sums in another order
  (three products a chunk); readings are 1e-7 - 1e-6 at these sizes.
  ``STATE_TOL`` 2e-5 of states of size ~3.
* ``F32_TOL`` 5e-5 (relative L2 of ``h_last`` / of the logits' largest): on
  f32 weights the program and the reference compute the same sums in
  another order; three layers read 2-7e-7.
* ``BF16_TOL`` 0.02 (``added_rel_err``: the error of the f32 residual
  stream at the last position over the norm of what the LAYERS ADDED to
  it): bf16 operands round to 3 significant digits; three pre-normed layers
  at hidden 64 read 0.003-0.007.  A program that leaves ANY ONE multiplier
  of the config out reads 0.03 or more (``MULT_TOL``), a dropped branch
  0.5.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import ssm_parallel as sp
from predictionio_tpu.models import ssm_parallel_reference as ref
from predictionio_tpu.ops import gated_delta as gd
from predictionio_tpu.ops import ssd_scan as ssd

SCAN_TOL, STATE_TOL, F32_TOL, BF16_TOL, MULT_TOL = 2e-5, 2e-5, 5e-5, 0.02, 0.03

# the published multipliers, but one that is 1 there (so that leaving it out
# shows) — and small widths
HF = dict(
    vocab_size=300, hidden_size=64, intermediate_size=96,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
    mamba_n_groups=2, mamba_d_state=32, mamba_d_conv=4, mamba_chunk_size=16,
    rope_theta=10000, rms_norm_eps=1e-5,
    attention_in_multiplier=0.8, attention_out_multiplier=0.0375,
    embedding_multiplier=5.656854249492381,
    key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    hidden_act="silu", attention_bias=False, mamba_conv_bias=True,
    mamba_proj_bias=False, mamba_rms_norm=True, mamba_norm_before_gate=False,
    mamba_use_mlp=True, mlp_bias=False, projectors_bias=False,
    attn_layer_indices=None, rope_scaling=None, tie_word_embeddings=False,
)
CFG = sp.SSMParallelConfig.from_hf(HF, max_len=64)
K = 10
H, G, P, N = 6, 2, 16, 32  # the scan's own tests: three heads a group


def _scan_inputs(seed, t):
    r = np.random.default_rng(seed)
    x = r.normal(size=(t, H * P))
    b, c = 0.3 * r.normal(size=(2, t, G * N))
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.5), size=(t, H)))
    a = -r.uniform(1, 16, size=H)  # decays 0.0003 ... 0.999
    d = r.normal(size=H)
    return [jnp.asarray(v, jnp.float32) for v in (x, b, c, dt, a, d)]


def _seg_start(lens):
    starts = np.cumsum([0] + list(lens[:-1]))
    return np.concatenate(
        [np.full(n, s) for n, s in zip(lens, starts)]).astype(np.int32)


def _recurrence(args, at, n, h0=None):
    x, b, c, dt, a, d = args
    y, h = ref.ssd_recurrence(
        x[at:at + n].reshape(n, H, P), b[at:at + n].reshape(n, G, N),
        c[at:at + n].reshape(n, G, N), dt[at:at + n], a, d, h0)
    return y.reshape(n, H * P), h


def _one_by_one(args, lens, h0=None):
    """Each row alone through the token-by-token recurrence."""
    outs, finals, at = [], [], 0
    for r, n in enumerate(lens):
        y, h = _recurrence(args, at, n, None if h0 is None else h0[r])
        outs.append(y)
        finals.append(h)
        at += n
    return jnp.concatenate(outs), jnp.stack(finals)


def _scan(args, seg, **kw):
    return ssd.ssd_scan(*args, jnp.asarray(seg), n_groups=G, interpret=True,
                        **kw)


# -- (a) the scan against the recurrence ---------------------------------------


@pytest.mark.parametrize("t, chunk", [(64, 16), (96, 32), (128, 64),
                                      (40, None), (64, 8)])
def test_chunked_scan_equals_the_recurrence(t, chunk):
    # one history as long as the axis: t = 40 is not a multiple of 128 (the
    # chunk becomes the axis), 96 = 3 x 32, and 64 / 8 crosses 8 chunks
    args = _scan_inputs(t, t)
    want, _ = _recurrence(args, 0, t)
    got = _scan(args, np.zeros(t, np.int32), chunk=chunk)
    assert float(jnp.abs(got - want).max()) < SCAN_TOL * float(
        jnp.abs(want).max())
    with pytest.raises(ValueError, match="multiple"):
        _scan(args, np.zeros(t, np.int32), chunk=7)


def test_a_head_reads_its_own_groups_b_and_c():
    # heads 0-2 read group 0, heads 3-5 group 1: with the groups' B and C
    # swapped every head's output moves
    t = 64
    x, b, c, dt, a, d = _scan_inputs(2, t)
    seg = np.zeros(t, np.int32)
    got = _scan([x, b, c, dt, a, d], seg, chunk=16)
    swap = lambda m: jnp.concatenate([m[:, N:], m[:, :N]], axis=1)
    other = _scan([x, swap(b), swap(c), dt, a, d], seg, chunk=16)
    flipped = jnp.concatenate(
        [x[:, H * P // 2:], x[:, :H * P // 2]], axis=1)
    # ... and equals the scan of the heads in the other order, put back
    back = _scan([flipped, b, c,
                  jnp.concatenate([dt[:, H // 2:], dt[:, :H // 2]], axis=1),
                  jnp.concatenate([a[H // 2:], a[:H // 2]]),
                  jnp.concatenate([d[H // 2:], d[:H // 2]])], seg, chunk=16)
    back = jnp.concatenate([back[:, H * P // 2:], back[:, :H * P // 2]], 1)
    assert float(jnp.abs(other - back).max()) < SCAN_TOL * 5
    per_head = jnp.abs(other - got).reshape(t, H, P).max(axis=(0, 2))
    assert float(per_head.min()) > 0.05


@pytest.mark.parametrize("t, chunk", [(256, 64), (128, 16)])
def test_the_state_is_carried_in_f32_from_chunk_to_chunk(t, chunk):
    """Heads that hardly decay (0.999 a token) over a history of several
    chunks: what a chunk hands on is still most of the state many chunks
    later.  On f32 inputs the kernel stays at the recurrence's rounding;
    the control — the same recurrence a chunk at a time, its state rounded
    to bf16 at every chunk's end — reads tens of times the limit.  (The
    benchmark's comparison cannot tell the two apart: at bf16 operands the
    model's ``h_last`` reads the same either way.  This test can.)"""
    x, b, c, _, _, d = _scan_inputs(t, t)
    dt = jnp.full((t, H), 1e-3, jnp.float32)
    a = -jnp.ones((H,), jnp.float32)
    args = [x, b, c, dt, a, d]
    want, _ = _recurrence(args, 0, t)
    rec = want - jnp.repeat(d, P)[None, :] * x  # what the STATE gave
    got = _scan(args, np.zeros(t, np.int32), chunk=chunk)
    scale = float(jnp.abs(rec).max())
    assert float(jnp.abs(got - want).max()) < SCAN_TOL * scale
    outs, state = [], None
    for at in range(0, t, chunk):
        y, state = _recurrence(args, at, chunk, state)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(y)
    low = jnp.concatenate(outs)
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
    got = _scan(args, _seg_start(lens), chunk=chunk)
    assert float(jnp.abs(got - want).max()) < SCAN_TOL * float(
        jnp.abs(want).max())


# -- (b') the padded tail is not run --------------------------------------------

REAL = (30, 1, 25)  # 56 tokens: three whole chunks of 16 and half a fourth


def _with_tail(tail_chunks, chunk=16):
    """REAL's rows, the part-filled last chunk's padding, then ``tail_chunks``
    whole chunks of padding: one-token histories, as ``pack`` lays them."""
    n_real = sum(REAL)
    t = -(-n_real // chunk) * chunk + tail_chunks * chunk
    args = _scan_inputs(11, 56 + 3 * chunk + 8)  # the same draws at every t
    lens = REAL + (1,) * (t - n_real)
    return ([a[:t] for a in args[:4]] + args[4:], _seg_start(lens), n_real, t)


@pytest.mark.parametrize("tail_chunks", [0, 1, 3])
def test_scan_equals_the_recurrence_before_a_padded_tail(tail_chunks):
    args, seg, n_real, t = _with_tail(tail_chunks)
    want, _ = _one_by_one(args, REAL)
    got = _scan(args, seg, chunk=16, n_real=jnp.int32(n_real))
    assert got.shape == (t, H * P)
    assert float(jnp.abs(got[:n_real] - want).max()) < SCAN_TOL * 5
    # the chunks past the last real token are not run: zeros, not garbage
    assert bool(jnp.isfinite(got).all())
    assert not bool(got[64:].any())
    assert ssd.scan_chunks(t, 16, n_real=n_real) == 4
    assert ssd.scan_chunks(t, 16) == 4 + tail_chunks


@pytest.mark.parametrize("tail_chunks", [1, 3])
def test_real_rows_do_not_change_by_a_bit_with_the_padded_tail(tail_chunks):
    short, seg0, n_real, _ = _with_tail(0)
    alone = _scan(short, seg0, chunk=16)
    args, seg, _, _ = _with_tail(tail_chunks)
    skipped = _scan(args, seg, chunk=16, n_real=n_real)
    scanned = _scan(args, seg, chunk=16)
    for got in (skipped, scanned):
        np.testing.assert_array_equal(got[:n_real], alone[:n_real])
    # a padded token that IS scanned is a history of its own
    assert bool(jnp.isfinite(scanned).all()) and bool(scanned[64:].any())


def test_counters_count_the_chunks_that_ran():
    own = sp.DispatchCounters(CFG)  # chunks of 16, three layers
    own.add(64, 1, 20, {})    # two of four chunks hold a real token
    own.add(64, 2, 64, {})
    own.add(128, 1, 65, {})   # five of eight
    st = own.stats()
    assert st["scan_chunks"] == 3 * (2 + 4 + 5)
    assert st["scan_tokens"] == 3 * (20 + 64 + 65)
    assert st["scan_rows"] == 3 * 4 and st["scan_chunk"] == 16
    assert st["scan_layers"] == st["attention_layers"] == 3


def test_convolution_with_a_bias_stops_at_a_rows_first_event():
    lens = (5, 1, 2, 9, 1, 1)
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(sum(lens), 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    seg = _seg_start(lens)
    positions = jnp.asarray(np.arange(len(seg)) - seg)
    got = ssd.causal_conv(x, w, positions, bias=bias, scope=ssd.CONV_SCOPE)
    want = jnp.concatenate([
        ref._conv(x[s:s + n], w, bias)
        for s, n in zip(np.cumsum((0,) + lens[:-1]), lens)])
    np.testing.assert_allclose(got, want, atol=1e-6)
    # a row's first token reads one tap and the bias
    np.testing.assert_allclose(got[8], x[8] * w[3] + bias, atol=1e-6)
    # without a bias it is the function the other family calls
    np.testing.assert_allclose(
        gd.causal_conv(x, w, positions) + bias, got, atol=1e-6)


def test_the_other_familys_convolution_is_the_parents_jaxpr_for_jaxpr():
    """``causal_conv`` gained ``bias`` and ``scope``; called as the
    gated-delta family calls it, it is the function as it stood (kept here,
    line for line)."""
    def before(x, w, positions):
        width = w.shape[0]
        xf = x.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        with jax.named_scope(gd.CONV_SCOPE):
            y = xf * wf[width - 1]
            for back in range(1, width):
                shifted = jnp.pad(xf, ((back, 0), (0, 0)))[:xf.shape[0]]
                inside = (positions >= back)[:, None]
                y = y + jnp.where(inside, shifted, 0.0) * wf[width - 1 - back]
            return y

    x = jnp.zeros((256, 48), jnp.float32)
    w = jnp.zeros((4, 48), jnp.bfloat16)
    pos = jnp.zeros((256,), jnp.int32)
    assert str(jax.make_jaxpr(gd.causal_conv)(x, w, pos)) == str(
        jax.make_jaxpr(before)(x, w, pos))


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
    seg = _seg_start(lens)
    starts = np.cumsum((0,) + whole[:-1]).astype(np.int32)
    lasts = (starts + np.array(whole) - 1).astype(np.int32)
    full, full_state = _scan(
        args, seg, chunk=chunk, row_start=jnp.asarray(starts),
        row_last=jnp.asarray(lasts), output_final_state=True)
    want, want_state = _one_by_one(args, whole)
    scale = float(jnp.abs(want).max())
    assert full_state.shape == (3, H, P, N)
    assert float(jnp.abs(full[:sum(whole)] - want).max()) < SCAN_TOL * scale
    assert float(jnp.abs(full_state - want_state).max()) < STATE_TOL * 3

    def packed(parts):
        """The named slices of every row end to end, padded to ``t``."""
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
    # the convolution's part of the carry: the last three inputs of A
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(t, 5)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(5,)), jnp.float32)
    y = ssd.causal_conv(x, w, jnp.arange(t) - seg, bias=bias)
    a_idx = np.concatenate([np.arange(s, s + c) for s, c in zip(starts, cut)])
    tail = ssd.conv_tail(x[a_idx], a_rs, a_rl, 4)
    assert tail.shape == (3, 3, 5)
    row_of = np.concatenate([np.full(n - c, r_) for r_, (c, n) in
                             enumerate(zip(cut, whole))])
    pos_b = np.asarray(b_seg)[:len(b_idx)]
    y_b = ssd.causal_conv(x[b_idx], w, jnp.arange(len(b_idx)) - pos_b,
                          tail=tail, row_of=jnp.asarray(row_of), bias=bias)
    np.testing.assert_allclose(y_b, y[b_idx], atol=1e-5)


# -- (d) the model against its plain reference ----------------------------------


@pytest.fixture(scope="module")
def weights():
    bf = sp.init_params(CFG, 3_000_000_007)
    return {"bf16": bf,
            "f32": {k: v.astype(jnp.float32) for k, v in bf.items()}}


def _histories(seed, lens):
    r = np.random.default_rng(seed)
    return [r.integers(0, CFG.vocab_size, n).astype(np.int32) for n in lens]


_WANT = {}


def _want(weights, h):
    """The plain reference's answer for one history on the f32 weights,
    computed once a module (the token-by-token recurrence compiles anew for
    every call)."""
    key = h.tobytes()
    if key not in _WANT:
        _WANT[key] = ref.reference_forward(CFG, weights["f32"], h)
    return _WANT[key]


def _program(cfg):
    @jax.jit
    def run(P, flat):
        return sp.forward_flat(cfg, P, flat, 128, K,
                               score_backend="reference")
    return run


@pytest.fixture(scope="module")
def program():
    return _program(CFG)


def _rel(got, want, over=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    over = want if over is None else np.asarray(over, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(over))


def _added_rel_err(cfg, P32, out, r, h, want):
    """The program's f32 residual stream of row ``r`` against the
    reference's, over what the layers added to the embedding."""
    x0 = cfg.embedding_multiplier * np.asarray(P32["embed"])[h[-1]]
    return _rel(out["x_last"][r], want["x_last"],
                np.asarray(want["x_last"]) - x0)


def test_packed_program_meets_the_reference_on_f32_weights(weights, program):
    hists = _histories(1, (37, 1, 70, 5))
    out = program(weights["f32"], jnp.asarray(sp.flatten(
        sp.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        want = _want(weights, h)
        assert _rel(out["h_last"][r], want["h_last"]) < F32_TOL
        assert _rel(out["x_last"][r], want["x_last"]) < F32_TOL
        logits = np.asarray(want["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=F32_TOL * np.abs(logits).max())


def test_packed_rows_equal_the_rows_alone(weights, program):
    hists = _histories(5, (50, 3, 40, 17))
    packed = program(weights["f32"], jnp.asarray(sp.flatten(
        sp.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        alone = program(weights["f32"], jnp.asarray(sp.flatten(
            sp.pack([h], 128, 8))))
        assert _rel(packed["x_last"][r], alone["x_last"][0]) < F32_TOL


def test_bf16_program_stays_within_rounding_and_the_controls_do_not(
        weights, program):
    hists = _histories(2, (64, 9, 33))
    out = program(weights["bf16"], jnp.asarray(sp.flatten(
        sp.pack(hists, 128, 8))))
    for r, h in enumerate(hists):
        want = _want(weights, h)
        assert _added_rel_err(CFG, weights["f32"], out, r, h, want) < BF16_TOL
        assert _rel(out["h_last"][r], want["h_last"]) < BF16_TOL
        logits = np.asarray(want["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=BF16_TOL * np.abs(logits).max())


MULTIPLIERS = (
    ["attention_in_multiplier", "attention_out_multiplier",
     "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
     "ssm_in_multiplier", "ssm_out_multiplier"]
    + [f"ssm_multipliers.{i}" for i in range(5)]
    + [f"mlp_multipliers.{i}" for i in range(2)])


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_leaving_any_one_multiplier_out_moves_the_output(weights, name):
    """The program with ONE multiplier of the config set to 1, against the
    reference that applies them all: past the tolerance the sound bf16
    program stays inside."""
    key, _, at = name.partition(".")
    if at:
        value = list(getattr(CFG, key))
        value[int(at)] = 1.0
        value = tuple(value)
    else:
        value = 1.0
    without = dataclasses.replace(CFG, **{key: value})
    hists = _histories(3, (48, 21))
    flat = jnp.asarray(sp.flatten(sp.pack(hists, 128, 8)))
    out = _program(without)(weights["bf16"], flat)
    worst = 0.0
    for r, h in enumerate(hists):
        want = _want(weights, h)
        worst = max(worst,
                    _added_rel_err(CFG, weights["f32"], out, r, h, want),
                    _rel(out["h_last"][r], want["h_last"]))
    assert worst > MULT_TOL, (name, worst)


def test_the_blocks_mixer_output_is_the_sum_of_its_two_branches(weights):
    """State-space part + attention part + residual = what the block hands
    its feed-forward, in the program and in the reference, and each part
    meets the reference's."""
    P32 = weights["f32"]
    h = _histories(4, (40,))[0]
    b = sp.pack([h], 64, 1)
    pos, seg = jnp.asarray(b["positions"]), jnp.asarray(b["seg_start"])
    x = CFG.embedding_multiplier * P32["embed"][b["tokens"]]
    W = ref.layer_weights(P32, 0)
    out, m_s, m_a = sp.layer(CFG, W, x, pos, seg, interpret=True)
    a = sp.rms_norm(x, W["in_norm"], CFG.rms_norm_eps)
    f = sp.rms_norm(x + m_s + m_a, W["ffn_norm"], CFG.rms_norm_eps)
    np.testing.assert_allclose(
        out, x + m_s + m_a + sp.mlp_branch(CFG, W, f), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        m_s, sp.ssm_branch(CFG, W, a, pos, seg, None, True), atol=1e-6)
    np.testing.assert_allclose(
        m_a, sp.attention_branch(CFG, W, a, pos, seg, True), atol=1e-6)
    want, want_s, want_a = ref.layer(CFG, W, x[:40])
    for got, wanted in ((m_s, want_s), (m_a, want_a), (out, want)):
        assert _rel(got[:40], wanted) < F32_TOL
    # each branch is a visible part of the sum (the seeded gains' purpose)
    for part in (want_s, want_a):
        assert 0.05 < float(jnp.linalg.norm(part) / jnp.linalg.norm(x[:40]))


def test_config_reads_the_published_keys_and_refuses_what_it_lacks():
    assert CFG.conv_width == 64 + 2 * 2 * 32 and CFG.ssm_in_width == 260
    assert CFG.rope_theta == 1e4 and isinstance(CFG.rope_theta, float)
    assert CFG.ssm_multipliers[3] == 0.5 and len(CFG.mlp_multipliers) == 2
    for key, bad in (("mamba_conv_bias", False), ("mamba_rms_norm", False),
                     ("mamba_norm_before_gate", True),
                     ("attn_layer_indices", [0, 2]), ("mamba_use_mlp", False),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            sp.SSMParallelConfig.from_hf({**HF, key: bad})
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        sp.SSMParallelConfig.from_hf({**HF, "mamba_d_ssm": 96})
    with pytest.raises(ValueError, match="multipliers"):
        sp.SSMParallelConfig.from_hf({**HF, "ssm_multipliers": [1.0] * 4})


def test_published_cut_counts_the_parameters_the_issue_states():
    full = dict(HF, vocab_size=261120, hidden_size=5120,
                intermediate_size=21504, num_hidden_layers=6,
                num_attention_heads=20, num_key_value_heads=4, head_dim=128,
                mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
                mamba_n_groups=2, mamba_d_state=256, mamba_chunk_size=128,
                rope_theta=100000000000)
    cfg = sp.SSMParallelConfig.from_hf(full)
    assert abs(cfg.layer_param_count() - 430.1e6) < 0.05e6
    assert abs(cfg.param_count() - 5254.6e6) < 0.05e6  # 10.51 GB in bf16
    shapes = sp.param_shapes(cfg)
    assert shapes["S.ssm_in"][0] == (6, 5120, 9248)  # z | x | B | C | dt
    assert shapes["S.conv"][0] == (6, 4, 5120)
    assert shapes["S.qkv"][0] == (6, 5120, 3584)
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) \
        == cfg.param_count() + (sp.padded_vocab(cfg) - 261120) * 5120
    assert cfg.rope_theta == 1e11


# -- (e) the ONE scorer class, the batcher and the query server -----------------


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
    assert st["family"] == "ssm_parallel_sequence"
    assert st["calls"] == 3 and st["queries"] == 7 and st["tokens"] == 189
    assert st["scan_tokens"] == 189 * 3 and st["scan_rows"] == 7 * 3
    assert st["causal_pairs"] == sum(n * (n + 1) // 2 for n in
                                     (5, 20, 17, 3, 60, 64, 20))
    assert "experts_touched" not in st and "linear_layers" not in st
    assert set(sc.forward(hists[:1])) >= {"values", "indices", "h_last",
                                          "x_last", "batch"}


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
    app_id = storage.get_meta_data_apps().insert(App(0, "ssmapp"))
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
        "datasource": {"params": {"appName": "ssmapp"}},
        "algorithms": [{"name": "ssmparallel", "params": {
            "appName": "ssmapp", "modelConfig": hf, "maxLen": 16, "seed": 5,
            "tokenLadder": [64, 128], "maxRows": 4, "maxK": 8}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "ssm", storage=storage, ctx=ctx)
    qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
    yield qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
    qs.stop()
    store_mod.set_storage(None)


def test_template_serves_ssmparallel_through_the_batcher(served):
    from predictionio_tpu.templates.sequentialrecommendation import (
        EventStoreHistory,
    )

    qs, base = served
    assert _http(base + "/readyz")["fastpathWarm"] is True
    fp = _http(base + "/")["fastpath"][0]
    assert fp["family"] == "ssm_parallel_sequence"
    assert fp["compile_count"] == 2 and fp["calls"] == 0
    model = qs._deployed.models[0]
    P32 = {k: jnp.asarray(v, jnp.float32) for k, v in model.params.items()}
    for u, num in ((0, 3), (5, 8), (3, 4)):
        ans = _http(base + "/queries.json", {"user": f"u{u}", "num": num})
        scores = [s["score"] for s in ans["itemScores"]]
        assert len(scores) == num and scores == sorted(scores, reverse=True)
        hist = EventStoreHistory("ssmapp", ("view", "buy", "rate")
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
    recs = _http(base + "/trace/dispatches.json")["dispatches"]
    assert recs[-1]["rung"] in (64, 128)


def test_train_refuses_a_published_width_and_shares_the_algorithm():
    from predictionio_tpu.templates import sequentialrecommendation as t

    assert t.SSMParallelAlgorithm.batch_predict is \
        t.LatentMoEAlgorithm.batch_predict
    assert t.SSMParallelAlgorithm.warmup is t.PackedSequenceAlgorithm.warmup
    algo = t.SSMParallelAlgorithm(t.PackedSequenceParams(modelConfig=dict(
        HF, hidden_size=5120, intermediate_size=21504, vocab_size=261120)))
    pd = type("PD", (), {"interactions": type("I", (), {
        "n_items": 100, "item_map": None})(), "histories": None})()
    with pytest.raises(NotImplementedError, match="no trainer"):
        algo.train(None, pd)
