"""The window/global-attention sparse-expert family at a small size on the
CPU: the streamed grouped-query kernel against plain attention, the sweep's
skip, the held experts' products against a dense loop, the share of one
expert layer over its ranks, the packed serving program against the plain
reference, and the family through the ONE scorer class and the template.

Tolerances, and why each:

* ``KERNEL_TOL`` 2e-5 (absolute, outputs of size ~1): on f32 inputs the
  online softmax computes the plain one's sums block by block; readings are
  3e-7.
* ``F32_TOL`` 2e-5 (relative L2 of ``x_last`` over what the layers added,
  and of the logits' largest): on f32 weights the program and the reference
  compute the same sums in another order; five sandwich-normed layers read
  1-5e-7.
* ``BF16_TOL`` 0.05 (the same measure): bf16 operands round to 3
  significant digits and every sublayer's output is normed, so an error is
  handed on undamped; five layers at hidden 64 read 0.005-0.01.  The
  controls — the window dropped, rotary on the global layer, key/value head
  ``h % 2`` — read above 0.2 and must fail it.
* ``SHARE_TOL`` 2e-6 (of the layer's largest output): the ranks' parts
  are f32 sums of the same products in another order; readings are 1-3e-7.
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
import token_tile_cases as cases

from predictionio_tpu.models import latent_moe as lm
from predictionio_tpu.models import window_moe as wm
from predictionio_tpu.models import window_moe_reference as ref_mod
from predictionio_tpu.models.window_moe_reference import reference_forward
from predictionio_tpu.ops import flash_attention as fa
from predictionio_tpu.ops import moe

KERNEL_TOL, F32_TOL, BF16_TOL, SHARE_TOL = 2e-5, 2e-5, 0.05, 2e-6
W, G = wm.WINDOW, wm.GLOBAL

HF = dict(
    vocab_size=300, hidden_size=64, num_hidden_layers=5, intermediate_size=96,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=16, layer_types=[W, W, G, W, W], num_dense_layers=1,
    num_shared_experts=1, route_norm=True, route_scale=2.448,
    rope_theta=10000, rms_norm_eps=1e-5, mup_enabled=True,
    score_func="sigmoid", hidden_act="silu", tie_word_embeddings=False,
    num_experts_held=4, first_expert_held=4,
)
CFG = wm.WindowMoEConfig.from_hf(HF, max_len=64)
K = 10


def _seg_start(lens, t):
    """Rows end to end, then a padded tail of one-token histories."""
    starts = np.cumsum([0] + list(lens[:-1]))
    seg = np.concatenate([np.full(n, s) for n, s in zip(lens, starts)])
    return np.concatenate([seg, np.arange(len(seg), t)]).astype(np.int32)


def _plain_attention(q, k, v, seg, window):
    hq, t, d = q.shape
    k, v = (np.repeat(a, hq // k.shape[0], axis=0) for a in (k, v))
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    low = seg[:, None] if window is None else np.maximum(
        seg[:, None], rows - window + 1)
    see = (cols <= rows) & (cols >= low)
    s = np.where(see[None], np.einsum("htd,hsd->hts", q, k) / np.sqrt(d),
                 -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hts,hsd->htd", p / p.sum(-1, keepdims=True), v), see


def _qkv(seed, hq, hkv, t, d):
    r = np.random.default_rng(seed)
    return (r.normal(size=(hq, t, d)).astype(np.float32),
            r.normal(size=(hkv, t, d)).astype(np.float32),
            r.normal(size=(hkv, t, d)).astype(np.float32))


# -- (a) the kernel ------------------------------------------------------------

# rows shorter than, equal to and longer than the window, a row boundary
# inside a window, a padded tail
LENS = (5, 16, 30, 3)


@pytest.mark.parametrize("window", [None, 1, 7, 8, 9, 16, 100])
@pytest.mark.parametrize("heads", [(4, 2), (6, 1), (3, 3)])
def test_grouped_kernel_equals_repeated_kv_plain_attention(window, heads):
    q, k, v = _qkv(1, *heads, 64, 16)
    seg = _seg_start(LENS, 64)
    got = fa.packed_grouped_attention(
        *(jnp.asarray(a) for a in (q, k, v, seg)), window=window, block=8)
    want, _ = _plain_attention(q, k, v, seg, window)
    np.testing.assert_allclose(got, want, atol=KERNEL_TOL)


@pytest.mark.parametrize("window, block", [(16, 8), (9, 8), (40, 16),
                                           (None, 8), (1, 8)])
def test_the_sweep_visits_the_key_blocks_the_mask_needs_and_no_other(
        window, block):
    """``sweep_blocks`` names, per query block, exactly the first key block
    the mask shows it; and the kernel reads no block outside ``[lo, qi]``: a
    value block it must not see is poisoned with NaN (``0 * NaN`` in ``p @
    v`` would spread), a block it must see cannot be skipped without
    changing the result."""
    t = 128
    seg = _seg_start((70, 9, 33), t)
    q, k, v = _qkv(2, 4, 2, t, 16)
    want, see = _plain_attention(q, k, v, seg, window)
    n_q = t // block
    by_block = see.reshape(n_q, block, n_q, block).any(axis=(1, 3))
    need_lo = by_block.argmax(axis=1)
    lo, to_start = fa.sweep_blocks(jnp.asarray(seg), block, window)
    np.testing.assert_array_equal(lo, need_lo)
    np.testing.assert_array_equal(to_start, seg[::block] // block)
    # every block from lo to the diagonal holds a visible pair
    for qi in range(n_q):
        assert by_block[qi, need_lo[qi]:qi + 1].all()
        assert not by_block[qi, :need_lo[qi]].any()
    visits = int(np.sum(np.arange(n_q) - need_lo + 1))
    assert visits == int(by_block.sum())
    if window is not None:
        assert fa.sweep_steps(t, block, window) >= int(
            (np.arange(n_q) - need_lo + 1).max())
        # every window here is shorter than the 70-event history: it skips
        assert visits < int(np.sum(np.arange(n_q) - np.asarray(to_start) + 1))
    # poison: each (query block, key block) pair outside the sweep, in turn
    # as one experiment per key block that SOME query block must not see
    for kb in range(n_q):
        blind = [qi for qi in range(n_q) if not need_lo[qi] <= kb <= qi]
        if not blind:
            continue
        vp = v.copy()
        vp[:, kb * block:(kb + 1) * block] = np.nan
        got = np.asarray(fa.packed_grouped_attention(
            *(jnp.asarray(a) for a in (q, k, vp, seg)), window=window,
            block=block))
        for qi in blind:
            rows = slice(qi * block, (qi + 1) * block)
            np.testing.assert_allclose(got[:, rows], want[:, rows],
                                       atol=KERNEL_TOL)


def test_a_long_history_costs_a_window_layer_a_windows_keys():
    """16 blocks of history under a window of 4 blocks: the sweep runs at
    most 5 key blocks a query block where a sweep to the start runs up to
    16."""
    seg = jnp.zeros((256,), jnp.int32)
    lo, to_start = fa.sweep_blocks(seg, 16, 64)
    qi = np.arange(16)
    assert int((qi - np.asarray(lo) + 1).max()) == 5 == fa.sweep_steps(
        256, 16, 64)
    assert int((qi - np.asarray(to_start) + 1).sum()) == 136
    assert int((qi - np.asarray(lo) + 1).sum()) == 1 + 2 + 3 + 4 + 12 * 5
    assert fa.sweep_steps(16384, 256, 4096) == 17
    assert fa.sweep_steps(16384, 256, None) == 64
    assert fa.sweep_steps(256, 256, 4096) == 1


def test_kernel_refuses_heads_that_do_not_group():
    q, k, v = _qkv(3, 4, 3, 16, 8)
    with pytest.raises(ValueError, match="multiple of the key/value heads"):
        fa.packed_grouped_attention(q, k, v, jnp.zeros(16, jnp.int32))
    q, k, v = _qkv(3, 4, 2, 24, 8)
    with pytest.raises(ValueError, match="multiple of the block"):
        fa.packed_grouped_attention(q, k, v, jnp.zeros(24, jnp.int32),
                                    block=16)


# -- (b) held experts ----------------------------------------------------------

T, D, F, E = 48, 16, 24, 16


@pytest.fixture(scope="module")
def layer():
    r = np.random.default_rng(5)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(r.normal(size=(T, D)))
    picked, w, _ = moe.route_sigmoid_topk(
        x, f32(r.normal(size=(D, E)) * 0.5), f32(r.normal(size=(E,)) * 0.01),
        top_k=4, scale=2.0)
    return {"x": x, "picked": picked, "w": w, "valid": jnp.arange(T) < 40,
            "w1": f32(r.normal(size=(E, D, F)) * 0.2),
            "w3": f32(r.normal(size=(E, D, F)) * 0.2),
            "w2": f32(r.normal(size=(E, F, D)) * 0.2)}


def _dense(L, first, n):
    """The held experts' part by a loop over tokens and picks."""
    y = np.zeros((T, D))
    for t in range(40):
        for j in range(4):
            e = int(L["picked"][t, j])
            if first <= e < first + n:
                h = jax.nn.silu(L["x"][t] @ L["w1"][e]) * (
                    L["x"][t] @ L["w3"][e])
                y[t] += float(L["w"][t, j]) * np.asarray(h @ L["w2"][e])
    return y


@pytest.mark.parametrize("rank", range(4))
@pytest.mark.parametrize("bound", [None, 8, 3],
                         ids=["default_bound", "two_passes", "many_passes"])
def test_held_products_are_exact_whatever_the_row_bound(layer, rank, bound):
    """Every assignment to a held expert is computed — also when the local
    assignments overflow the rows one pass gathers — and none to an expert
    held elsewhere; ``counts`` is over the held experts."""
    L, lo = layer, 4 * rank
    held = lambda w: w[lo:lo + 4]
    y, counts = moe.expert_products(
        L["x"], L["picked"], L["w"], held(L["w1"]), held(L["w3"]),
        held(L["w2"]), L["valid"], first=lo, n_experts=E,
        max_local_rows=bound)
    want = _dense(L, lo, 4)
    np.testing.assert_allclose(y, want, atol=SHARE_TOL * np.abs(want).max())
    _, all_counts = moe.expert_products(
        L["x"], L["picked"], L["w"], L["w1"], L["w3"], L["w2"], L["valid"])
    np.testing.assert_array_equal(counts, all_counts[lo:lo + 4])
    if bound == 3:
        assert int(counts.sum()) > 2 * 3  # the overflow path really ran


def test_all_experts_held_is_todays_function_bit_for_bit():
    """``first=0, n_experts=E`` on JoyAI's rehearsal widths: the same
    jaxpr as the call without them, and the result of the function as it
    stood before it was told what it holds (kept here, line for line)."""
    def before(x, picked, weights, w1, w3, w2, valid):
        t, _ = x.shape
        n_experts = w1.shape[0]
        k = picked.shape[1]
        with jax.named_scope(moe.EXPERTS_SCOPE):
            flat = picked.reshape(-1)
            flat = jnp.where(jnp.repeat(valid, k), flat, n_experts)
            counts = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)[
                :n_experts]
            order = jnp.argsort(flat, stable=True)
            xs = x[order // k]
            gate = moe.grouped_matmul(xs, w1, counts)
            up = moe.grouped_matmul(xs, w3, counts)
            h = (jax.nn.silu(gate) * up).astype(x.dtype)
            ys = moe.grouped_matmul(h, w2, counts)
            back = jnp.argsort(order)
            yk = ys[back].reshape(t, k, -1)
            y = jnp.einsum("tkd,tk->td", yk, weights.astype(jnp.float32))
            return jnp.where(valid[:, None], y, 0.0), counts

    r = np.random.default_rng(7)
    t, d, f, e, k = 64, 64, 32, 16, 4  # joyai-llm-flash-l5's rehearsal widths
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    x = bf(r.normal(size=(t, d)))
    picked, w, _ = moe.route_sigmoid_topk(
        x, jnp.asarray(r.normal(size=(d, e)), jnp.float32),
        jnp.zeros((e,), jnp.float32), top_k=k, scale=2.5)
    args = (x, picked, w, bf(r.normal(size=(e, d, f)) * 0.1),
            bf(r.normal(size=(e, d, f)) * 0.1),
            bf(r.normal(size=(e, f, d)) * 0.1), jnp.arange(t) < 50)
    told = lambda *a: moe.expert_products(*a, first=0, n_experts=e)
    assert str(jax.make_jaxpr(told)(*args)) == str(
        jax.make_jaxpr(moe.expert_products)(*args)) == str(
        jax.make_jaxpr(before)(*args))
    for got, want in zip(jax.jit(told)(*args), jax.jit(before)(*args)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiles_and_bounds_at_the_published_widths():
    # an expert's 3,072 x 3,072 matrix does not fit VMEM whole: column tiles
    assert moe._col_tile(3072, 3072, 2) == 512
    # every shape the benchmark had keeps its whole-matrix tile
    assert moe._col_tile(2048, 768, 2) == 768
    assert moe._col_tile(768, 2048, 2) == 2048
    # twice the held experts' even share, in row tiles, never above T * k
    assert moe.local_row_bound(4 * 16384, 32, 256) == 16384
    assert moe.local_row_bound(4 * 256, 32, 256) == 256
    assert moe.local_row_bound(4 * 256, 256, 256) == 1024
    assert moe.local_row_bound(128, 4, 16) == 128


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """THE SHARE TEST.  One expert layer of the model, all 16 experts, in
    the plain reference; and the parts the 4 ranks give — each the program's
    held-expert products for its slice — with the shared expert counted
    once: equal within f32 rounding.  So do the reference's own shares."""
    whole = wm.WindowMoEConfig.from_hf(
        {**HF, "num_experts_held": None, "first_expert_held": 0})
    P = {k: v.astype(jnp.float32)
         for k, v in wm.init_params(whole, 11).items()}
    Wl = ref_mod.layer_weights(P, 2)
    m = jnp.asarray(np.random.default_rng(3).normal(size=(40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, picked, _ = ref_mod.expert_layer(whole, Wl, m)
        shared = ref_mod._swiglu(m, Wl["s_w1"], Wl["s_w3"], Wl["s_w2"])
        own, w, _ = moe.route_sigmoid_topk(
            m, Wl["gate"], Wl["gate_bias"], top_k=4, scale=2.448)
        np.testing.assert_array_equal(np.sort(own, 1), np.sort(picked, 1))
        parts = ref_parts = shared
        for rank in range(4):
            lo = 4 * rank
            cut = {**Wl, **{n: Wl[n][lo:lo + 4]
                            for n in ("e_w1", "e_w3", "e_w2")}}
            y, _ = moe.expert_products(
                m, own, w, cut["e_w1"], cut["e_w3"], cut["e_w2"],
                first=lo, n_experts=16)
            parts = parts + y
            ref_parts = ref_parts + ref_mod.expert_layer(
                whole, cut, m, first=lo, shared=False)[0]
    size = float(jnp.abs(uncut).max())
    np.testing.assert_allclose(parts, uncut, atol=SHARE_TOL * size)
    np.testing.assert_allclose(ref_parts, uncut, atol=SHARE_TOL * size)
    # the experts matter, and so does each rank's part
    assert float(jnp.abs(uncut - shared).max()) > 0.3 * size
    assert float(jnp.abs(y).max()) > 0.05 * size


# -- (c) the model against its plain reference ---------------------------------


@pytest.fixture(scope="module")
def weights():
    bf = wm.init_params(CFG, 3_900_000_007)
    return {"bf16": bf,
            "f32": {k: v.astype(jnp.float32) for k, v in bf.items()}}


def _histories(seed, lens):
    r = np.random.default_rng(seed)
    return [r.integers(0, CFG.vocab_size, n).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def program():
    @jax.jit
    def run(P, flat):
        return wm.forward_flat(CFG, P, flat, 128, K,
                               score_backend="reference")
    return run


def _row_picks(out, batch, r):
    hi = int(batch["last_idx"][r])
    return np.asarray(out["picks"][:, int(batch["seg_start"][hi]):hi + 1])


def _added_err(out, r, ref):
    """The error of the residual stream at the last position over what the
    layers added to it (the benchmark's ``added_rel_err``)."""
    got = np.asarray(out["x_last"][r], np.float64)
    want = np.asarray(ref["x_last"], np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(
        want - np.asarray(ref["x0_last"], np.float64)))


# shorter than the window (16), equal to it, longer; boundaries inside a
# window; a padded tail of 35 tokens
@pytest.mark.parametrize("lens", [(7, 16, 40, 30), (64, 1, 17), (16, 16, 15)],
                         ids=lambda x: "-".join(map(str, x)))
def test_packed_program_meets_the_reference_on_f32_weights(
        weights, program, lens):
    hists = _histories(1, lens)
    batch = wm.pack(hists, 128, 8)
    out = program(weights["f32"], jnp.asarray(wm.flatten(batch)))
    for r, h in enumerate(hists):
        ref = reference_forward(CFG, weights["f32"], h,
                                picks=_row_picks(out, batch, r))
        assert float(ref["violation"].max()) < 1e-5
        assert _added_err(out, r, ref) < F32_TOL
        logits = np.asarray(ref["logits"], np.float64)
        np.testing.assert_allclose(
            out["values"][r], np.sort(logits)[::-1][:K],
            atol=F32_TOL * np.abs(logits).max())


def test_bf16_program_stays_within_rounding_and_the_controls_do_not(
        weights, program, monkeypatch):
    hists = _histories(2, (64, 9, 33))
    batch = wm.pack(hists, 128, 8)
    out = program(weights["bf16"], jnp.asarray(wm.flatten(batch)))
    sound = [reference_forward(CFG, weights["bf16"], h,
                               picks=_row_picks(out, batch, r))
             for r, h in enumerate(hists)]
    for r in range(3):
        assert _added_err(out, r, sound[r]) < BF16_TOL
        assert float(sound[r]["violation"].max()) < 0.02

    def wrong(r, **cfg_change):
        cfg = dataclasses.replace(CFG, **cfg_change)
        return _added_err(out, r, reference_forward(
            cfg, weights["bf16"], hists[r], picks=_row_picks(out, batch, r)))

    # the window dropped: only a row longer than the window can tell
    assert wrong(0, sliding_window=10 ** 6) > 4 * BF16_TOL
    assert wrong(1, sliding_window=10 ** 6) < BF16_TOL
    # rotary on the global layer too: every layer a window layer as wide
    # as any history
    assert wrong(2, layer_types=(W,) * 5, sliding_window=10 ** 6) > \
        4 * BF16_TOL
    # key/value head h % 2 for h // 2
    real = jnp.repeat
    monkeypatch.setattr(
        ref_mod.jnp, "repeat",
        lambda z, n, axis: jnp.concatenate([z] * n, axis=axis))
    assert _added_err(out, 2, reference_forward(
        CFG, weights["bf16"], hists[2],
        picks=_row_picks(out, batch, 2))) > 4 * BF16_TOL
    monkeypatch.setattr(ref_mod.jnp, "repeat", real)


def test_rope_turns_the_window_layers_only():
    """Positions stretched by two change what a window layer computes and
    leave a global layer's output as it was, bit for bit."""
    def one_layer(kind):
        cfg = wm.WindowMoEConfig.from_hf(
            {**HF, "num_hidden_layers": 1, "layer_types": [kind],
             "sliding_window": 64})
        P = {k: v.astype(jnp.float32)
             for k, v in wm.init_params(cfg, 3).items()}
        b = wm.pack(_histories(4, (40,)), 64, 2)
        run = lambda pos: np.asarray(wm.trunk(
            cfg, P, b["tokens"], jnp.asarray(pos), b["seg_start"],
            jnp.asarray(b["valid"]))[0])
        return run(b["positions"]), run(2 * b["positions"])

    plain, stretched = one_layer(G)
    np.testing.assert_array_equal(plain, stretched)
    plain, stretched = one_layer(W)
    assert np.abs(plain - stretched)[:40].max() > 1e-3


def test_half_rotation_is_the_reference_rotation():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(9, 3, 16)),
                    jnp.float32)
    np.testing.assert_allclose(
        wm.rope_half(x, jnp.arange(9), 10000.0),
        ref_mod._rope_half(x, 10000.0), atol=2e-6)
    # position 0 turns nothing; the pairs are (i, i + d/2), not (2i, 2i+1)
    np.testing.assert_array_equal(
        wm.rope_half(x, jnp.zeros(9, jnp.int32), 10000.0), x)
    assert np.abs(np.asarray(wm.rope_half(x, jnp.arange(9), 10000.0))
                  - np.asarray(lm.rope_interleaved(
                      x.transpose(1, 0, 2), jnp.arange(9), 10000.0
                  ).transpose(1, 0, 2))).max() > 0.1


def test_a_token_with_no_held_pick_gets_the_shared_expert_alone(weights):
    """Routing stays over all 16; a token none of whose 4 picks lies in
    [4, 8) adds nothing from the held experts, and is counted."""
    P = weights["f32"]
    r = np.random.default_rng(8)
    m = jnp.asarray(r.normal(size=(32, 64)), jnp.float32)
    valid = jnp.arange(32) < 30
    f, picked, counts, unheld = wm._sparse_ffn(
        CFG, None, wm.layer_weights(P, 2, wm.ROUTE + wm.EXPERTS + wm.FFN_OUT),
        m, valid)
    assert int(picked.max()) > 7 and int(picked.min()) < 4  # over all 16
    none = ~((np.asarray(picked) >= 4) & (np.asarray(picked) < 8)).any(1)
    assert none[:30].sum() > 0 and int(unheld) == int(none[:30].sum())
    shared = lm._swiglu(m, P["L2.s_w1"], P["L2.s_w3"], P["L2.s_w2"])
    np.testing.assert_allclose(np.asarray(f)[none], np.asarray(shared)[none],
                               atol=1e-6)
    some = ~none & np.asarray(valid)
    assert np.abs(np.asarray(f) - np.asarray(shared))[some].min(0).max() > 0
    held_picks = ((np.asarray(picked) >= 4) & (np.asarray(picked) < 8)
                  & np.asarray(valid)[:, None]).sum()
    assert int(counts.sum()) == int(held_picks) and counts.shape == (4,)


def test_config_reads_the_slice_and_refuses_what_it_does_not_implement():
    assert CFG.n_held == 4 and CFG.n_moe_layers == 4
    assert CFG.n_window_layers == 4 and CFG.qkvg_width == 2 * 16 * 6
    whole = wm.WindowMoEConfig.from_hf(
        {k: v for k, v in HF.items() if "held" not in k})
    assert whole.n_held == 16 and whole.first_expert_held == 0
    for key, bad in (("score_func", "softmax"), ("n_group", 2),
                     ("rope_scaling", {"type": "yarn"}),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            wm.WindowMoEConfig.from_hf({**HF, key: bad})
    with pytest.raises(ValueError, match="layer_types"):
        wm.WindowMoEConfig.from_hf({**HF, "num_hidden_layers": 4})
    with pytest.raises(ValueError, match="key/value heads"):
        wm.WindowMoEConfig.from_hf({**HF, "num_key_value_heads": 3})
    with pytest.raises(ValueError, match="not among the router's"):
        wm.WindowMoEConfig.from_hf({**HF, "first_expert_held": 14})


def test_published_cut_counts_the_parameters_the_issue_states():
    full = dict(HF, vocab_size=200192, hidden_size=3072,
                intermediate_size=12288, moe_intermediate_size=3072,
                num_experts=256, num_attention_heads=48,
                num_key_value_heads=8, head_dim=128, sliding_window=4096,
                num_experts_held=32, first_expert_held=0)
    cfg = wm.WindowMoEConfig.from_hf(full, max_len=16384)
    assert abs(cfg.param_count() - 5.398e9) < 0.001e9  # 10.80 GB in bf16
    shapes = wm.param_shapes(cfg)
    assert shapes["L0.qkvg"][0] == (3072, 14336)  # 6,144 + 1,024 x 2 + 6,144
    assert shapes["L0.w1"][0] == (3072, 12288) and "L0.gate" not in shapes
    assert shapes["L1.e_w1"][0] == (32, 3072, 3072)
    assert shapes["L1.gate"][0] == (3072, 256)  # the router keeps its width
    whole = wm.WindowMoEConfig.from_hf(
        {**full, "num_experts_held": None}, max_len=16384)
    layer = (whole.param_count() - cfg.param_count()) / 4 + 32 * 28.31e6
    assert abs(layer - 7.25e9) < 0.01e9  # 256 experts: one layer, 14.5 GB


# -- (d) the family through the ONE scorer class and the template --------------


def test_the_one_scorer_class_serves_the_family_with_its_counters(weights):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    sc = PackedSequenceScorer(CFG, weights["f32"], max_k=K,
                              ladder=(64, 128), max_rows=4)
    assert sc.compile_count == 2 and sc.warmup_executions == 2
    hists = _histories(10, (5, 20, 17, 3, 60, 64, 20))  # 3 dispatches
    idx, vals = sc.score_topk(hists, 5)
    assert idx.shape == (7, 5) and sc.compile_count == 2
    one = sc.forward(hists[4:5])
    assert set(one) >= {"values", "indices", "h_last", "x_last", "picks",
                        "expert_counts", "tokens_unheld", "attn_counts",
                        "batch"}
    ref = reference_forward(CFG, weights["f32"], hists[4],
                            picks=one["picks"][:, :60])
    assert _added_err(one, 0, ref) < F32_TOL
    st = sc.stats()
    assert st["family"] == "window_moe_sequence"
    assert st["calls"] == 3 and st["queries"] == 7 and st["tokens"] == 189
    assert (st["experts"], st["experts_held"], st["first_expert_held"]) == (
        16, 4, 4)
    assert st["routed_assignments"] == 189 * 4 * 4
    # 4 of 16 held: a quarter of the assignments, give or take the draw
    assert 0.15 < st["expert_assignments"] / st["routed_assignments"] < 0.35
    assert 0 < st["tokens_without_held_expert"] < 189 * 4
    assert st["experts_touched"] <= 3 * 4 * 4
    assert st["sparse_layer_dispatches"] == 3 * 4
    assert st["local_row_overflows"] == 0
    # min(position + 1, 16) a token a window layer; position + 1 a global
    want_w = sum(sum(min(p + 1, 16) for p in range(len(h))) for h in hists)
    want_g = sum(len(h) * (len(h) + 1) // 2 for h in hists)
    assert st["window_pairs"] == 4 * want_w
    assert st["global_pairs"] == want_g == st["causal_pairs"]
    # one block a rung here (block = min(256, rung)): nothing to skip
    assert st["window_kv_blocks"] == st["window_kv_blocks_unskipped"] == 3 * 4
    assert "scan_tokens" not in st  # the hybrid's, not everyone's


def test_counters_count_the_blocks_the_window_skips():
    cfg = wm.WindowMoEConfig.from_hf({**HF, "sliding_window": 300},
                                     max_len=1024)
    b = wm.pack([np.zeros(900, np.int32), np.zeros(70, np.int32)], 1024, 4)
    got = np.asarray(wm.attention_counts(
        cfg, jnp.asarray(b["positions"]), jnp.asarray(b["seg_start"]),
        jnp.asarray(b["valid"])))
    assert got[0] == sum(min(p + 1, 300) for p in range(900)) + 70 * 71 // 2
    assert got[1] == 900 * 901 // 2 + 70 * 71 // 2
    # blocks of 256: query blocks 0..3 hold real tokens; a sweep to the
    # start runs 1 + 2 + 3 + 4, the window's 1 + 2 + 3 + 3 (block 3 starts
    # at token 768 and sees back to 469, in block 1)
    assert (got[2], got[3]) == (9, 10)


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
    app_id = storage.get_meta_data_apps().insert(App(0, "wmoeapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(11)
    events, t = [], 0
    for u in range(6):
        for i in rng.integers(0, 40, size=3 + 5 * u):
            t += 1
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                event_time=f"2026-01-01T00:{t // 60:02d}:{t % 60:02d}Z"))
    le.batch_insert(events, app_id)
    engine = SequentialRecommendationEngine.apply()
    hf = {k: v for k, v in HF.items() if k != "vocab_size"}
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "wmoeapp"}},
        "algorithms": [{"name": "windowmoe", "params": {
            "appName": "wmoeapp", "modelConfig": hf, "maxLen": 24, "seed": 5,
            "tokenLadder": [64, 128], "maxRows": 4, "maxK": 8}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "wmoe", storage=storage, ctx=ctx)
    qs = QueryServer(engine, storage=storage, ctx=ctx, batching=True)
    yield qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"
    qs.stop()
    store_mod.set_storage(None)


def test_template_serves_windowmoe_through_the_batcher(served):
    """``pio deploy --batching`` with the family: ``POST /queries.json``
    answers what the direct program call gives for the same history."""
    from predictionio_tpu.templates.sequentialrecommendation import (
        EventStoreHistory,
    )

    qs, base = served
    assert _http(base + "/readyz")["fastpathWarm"] is True
    fp = _http(base + "/")["fastpath"][0]
    assert fp["family"] == "window_moe_sequence"
    assert fp["compile_count"] == 2 and fp["calls"] == 0
    model = qs._deployed.models[0]
    scorer = qs._deployed.algorithms[0]._scorer(model)
    for u, num in ((0, 3), (5, 8), (3, 4)):
        ans = _http(base + "/queries.json", {"user": f"u{u}", "num": num})
        scores = [s["score"] for s in ans["itemScores"]]
        assert len(scores) == num and scores == sorted(scores, reverse=True)
        hist = EventStoreHistory("wmoeapp", ("view", "buy", "rate")
                                 ).recent_indices(f"u{u}", 24, model.item_map)
        direct = scorer.forward([hist])
        assert [model.item_map[s["item"]] for s in ans["itemScores"]] == \
            direct["indices"][0][:num].tolist()
        np.testing.assert_array_equal(
            np.asarray(scores, np.float32), direct["values"][0][:num])
    assert _http(base + "/queries.json",
                 {"user": "nobody", "num": 3}) == {"itemScores": []}
    after = _http(base + "/")["fastpath"][0]
    assert after["compile_count"] == 2 and after["calls"] == 3
    assert after["sparse_layer_dispatches"] == 3 * 4
    assert after["window_pairs"] > 0 and after["routed_assignments"] > 0
    # the dense sublayers' tiles: in `GET /` and as `pio_fastpath_*` (both
    # rungs here hold no more than two tiles of 512, so each dispatch ran one)
    assert after["dense_tiles"] == after["dense_tiles_rung"] == 3
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert "pio_fastpath_dense_tiles_total 3" in text
    assert "pio_fastpath_dense_tiles_rung_total 3" in text
    recs = _http(base + "/trace/dispatches.json")["dispatches"]
    assert recs[-1]["rung"] in (64, 128)


def test_train_refuses_a_published_width_and_shares_the_algorithm():
    from predictionio_tpu.templates import sequentialrecommendation as t

    assert t.WindowMoEAlgorithm.batch_predict is \
        t.LatentMoEAlgorithm.batch_predict
    assert t.WindowMoEAlgorithm.warmup is t.PackedSequenceAlgorithm.warmup
    assert t.SequentialRecommendationEngine.apply().algorithm_cls_map[
        "windowmoe"] is t.WindowMoEAlgorithm
    algo = t.WindowMoEAlgorithm(t.PackedSequenceParams(modelConfig=dict(
        HF, hidden_size=3072, intermediate_size=12288,
        moe_intermediate_size=3072, vocab_size=200192)))
    pd = type("PD", (), {"interactions": type("I", (), {
        "n_items": 100, "item_map": None})(), "histories": None})()
    with pytest.raises(NotImplementedError, match="no trainer"):
        algo.train(None, pd)


# -- the dense sublayers in token tiles (ops/token_tiles) ------------------------


@pytest.fixture(scope="module")
def tile_programs():
    return cases.programs(wm, CFG)


@pytest.mark.parametrize("lens", cases.LENS, ids=str)
def test_in_token_tiles_every_real_row_is_the_whole_rungs(
        weights, tile_programs, lens):
    whole, tiled = cases.check_real_rows(
        wm, CFG, weights["f32"], lens, tile_programs, exact=("expert_counts", "tokens_unheld", "attn_counts"))
    # the routing of every REAL token too (a padded token's is no output)
    n_tok = sum(lens)
    np.testing.assert_array_equal(tiled["picks"][:, :n_tok],
                                  whole["picks"][:, :n_tok])


@pytest.mark.parametrize("lens", cases.LENS, ids=str)
def test_the_trunk_runs_the_tiles_that_hold_a_real_token_and_no_other(
        weights, lens):
    b = wm.pack(cases.histories(CFG, 22, lens), cases.T, cases.ROWS)
    x = wm.trunk(CFG, weights["f32"], b["tokens"], b["positions"],
                 b["seg_start"], jnp.asarray(b["valid"]),
                 dense_tile=cases.TILE)[0]
    cases.check_trip_count(x, sum(lens))


@pytest.mark.parametrize("t", fingerprints.RUNGS["window_moe"])
def test_a_rung_of_two_tiles_or_fewer_is_the_parents_program_jaxpr_for_jaxpr(
        t):
    """The 256-, 512- and 1,024-token programs at the program's own tile,
    by their fingerprints: the text as PR 48 left it (the layers' equal
    branches one ``pjit`` each; the equations are PR 41's, held to the bit
    below); from 2,048 tokens the loops are there."""
    assert fingerprints.fingerprint("window_moe", CFG, t) == fingerprints.PARENT[
        f"window_moe.{t}"]
    low, high = (fingerprints.program_jaxpr("window_moe", CFG, n).count(
        "dynamic_update_slice") for n in (t, 2048))
    assert high > low


# -- equal residual branches are one traced and lowered function (PR 48) ---------


TILED = dict(dense_tile=cases.TILE)


@pytest.mark.parametrize("what, t, kw", [
    ("counts", cases.T, dict(distinct=4, calls=2 * CFG.num_hidden_layers)),
    ("counts", cases.T, dict(distinct=8, calls=23, **TILED)),
    ("bits", 64, {}), ("bits", cases.T, {}), ("bits", cases.T, TILED),
    ("lowered", 256, dict(kernels=5, unshared_kernels=17)),
    ("lowered", 2048, dict(kernels=5, unshared_kernels=17)),  # in tiles
])
def test_the_layers_share_what_they_run_with_an_equal_signature(
        what, t, kw, weights, monkeypatch):
    """``[W, W, G, W, W]`` with one dense layer.  A rung run whole: window
    attention, global attention, the dense and the sparse feed-forward — 4
    bodies for 2 x 5 calls; of the kernels two attentions and the sparse
    branch's three grouped products, where the layers' own come to 5 + 4 x
    3.  A rung in tiles shares part by part: the segment before the kernel
    and the kernel by kind, the segment between by dense or sparse, the
    products and the segment behind them — 8 bodies for 3 x 5 + 2 x 4
    calls, and the same five kernels."""
    branches.check(what, monkeypatch, wm, CFG, weights["bf16"], t, **kw)


@pytest.mark.parametrize("t_pad, n_tok, ran, rung", [
    (256, 100, 1, 1), (512, 512, 1, 1), (1024, 513, 1, 1),  # run whole
    (2048, 1025, 3, 4), (2048, 2048, 4, 4), (4096, 2049, 5, 8),
    (16384, 8193, 17, 32), (16384, 16384, 32, 32),
])
def test_the_familys_counters_add_the_tiles_the_program_ran(
        t_pad, n_tok, ran, rung):
    """``runs_in_tiles`` decides for the program (``trunk``) and for the
    counter alike; a rung run whole counts as one tile, run."""
    assert wm.runs_in_tiles(t_pad) == (rung > 1)
    own = wm.DispatchCounters(CFG)
    got = {"expert_counts": np.ones((CFG.n_moe_layers, CFG.n_held), np.int32),
           "tokens_unheld": np.zeros(CFG.n_moe_layers, np.int32),
           "attn_counts": np.zeros(4, np.int32)}
    for _ in range(2):
        own.add(t_pad, 1, n_tok, got)
    st = own.stats()
    assert st["dense_tile"] == 512
    assert (st["dense_tiles"], st["dense_tiles_rung"]) == (2 * ran, 2 * rung)


def test_through_the_scorer_a_rung_in_tiles_answers_as_a_rung_run_whole(
        weights):
    """At the program's own tile, through the ONE scorer class: 1,200 tokens
    in a rung of 2,048 run three tiles of four, and every row is answered as
    the 1,024 rung's program, which runs whole, answers it."""
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    hists = _histories(23, (60,) * 20)
    build = lambda rung: PackedSequenceScorer(
        CFG, weights["f32"], max_k=K, ladder=(rung,), max_rows=32)
    tiled, whole = build(2048), build(1024)
    idx, vals = tiled.score_topk(hists, 5)
    st = tiled.stats()
    assert (st["calls"], st["dense_tiles"], st["dense_tiles_rung"]) == (1, 3, 4)
    want = [whole.score_topk(hists[lo:lo + 10], 5) for lo in (0, 10)]
    st = whole.stats()
    assert (st["calls"], st["dense_tiles"], st["dense_tiles_rung"]) == (2, 2, 2)
    np.testing.assert_array_equal(idx, np.concatenate([w[0] for w in want]))
    np.testing.assert_allclose(vals, np.concatenate([w[1] for w in want]),
                               rtol=1e-4, atol=1e-6)
