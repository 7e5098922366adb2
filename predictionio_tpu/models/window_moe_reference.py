"""The plain reference of ``models/window_moe.py``: the public ``afmoe``
layer equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, ONE history at a time, the full ``(T, T)`` attention
matrix with the window as a mask, a Python loop over the held experts with
masked weights; no packing, no kernels, nothing imported from the serving
program.

Reads the program's parameter dict (``L<i>.<name>``; ``qkvg`` is ``[W_q |
W_k | W_v | W_g]``; ``e_w1 / e_w3 / e_w2`` hold the router's experts
``[first_expert_held, first_expert_held + n_held)``).

What the config's keys do not pin and the public implementation supplies
(the configuration file lists each under ``assumed``):

* the attention output is gated, ``(softmax(..) v) * sigmoid(a W_g)``, with
  ``W_g`` a projection of the normed input as wide as the query heads;
* rotary embedding (half rotation, ``rope_theta``) on ``sliding_attention``
  layers ONLY; a ``full_attention`` layer has none;
* four norms a layer, sandwich order: ``x + Norm(attn(Norm(x)))`` then ``x +
  Norm(mlp(Norm(x)))``; q and k RMS-normed per head over ``head_dim``;
* ``mup_enabled``: the embedding is multiplied by ``sqrt(hidden_size)``;
* experts are SELECTED by ``sigmoid + bias`` and WEIGHED by the unbiased
  sigmoid, normalised (``route_norm``, ``+ 1e-20``) and times
  ``route_scale``.

Departures, each deliberate:

* **held experts**: the part of ``sum_j w_j SwiGLU_{e_j}`` whose experts are
  not held (``e_j`` outside the slice) is LEFT OUT, as in the program: the
  reference is given the same experts, and nothing stands in for the rest.
  Routing, the weights ``w_j`` and their normalisation are over all the
  router's experts either way.  :func:`expert_layer` computes one layer for
  any slice, which is what ties the share to the whole model (the parts of
  all the slices, the shared expert counted once, add up to the uncut
  layer);
* ``picks`` may be FORCED (the experts a program under test selected), as
  in ``latent_moe_reference``: the weights are then still this reference's
  own unbiased scores; ``violation`` says how far below this reference's
  own ``top_k``-th best ``sigma + bias`` the worst forced pick lies,
  unforced ``margin`` is the gap between the ``top_k``-th and the next.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _rope_half(x, theta):
    """``rotate_half`` at positions 0..T-1; ``x`` (T, heads, d)."""
    t, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)[:, None, :]  # (T, 1, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * np.cos(ang) + rot * np.sin(ang)


def attention_layer(cfg, W, x, kind):
    """One gated grouped-query attention sublayer's output BEFORE its post
    norm; ``W`` one layer's tensors, ``x`` (T, hidden)."""
    t = x.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    a = _rms(x, _f32(W["in_norm"]), cfg.rms_norm_eps)
    qkvg = a @ _f32(W["qkvg"])
    q_end, k_end, v_end = hq * hd, (hq + hkv) * hd, (hq + 2 * hkv) * hd
    q = _rms(qkvg[:, :q_end].reshape(t, hq, hd), _f32(W["q_norm"]),
             cfg.rms_norm_eps)
    k = _rms(qkvg[:, q_end:k_end].reshape(t, hkv, hd), _f32(W["k_norm"]),
             cfg.rms_norm_eps)
    v = qkvg[:, k_end:v_end].reshape(t, hkv, hd)
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    see = cols <= rows
    if kind == "sliding_attention":
        q, k = _rope_half(q, cfg.rope_theta), _rope_half(k, cfg.rope_theta)
        see = see & (cols > rows - cfg.sliding_window)
    # query head h reads key/value head h // (hq / hkv)
    k, v = (jnp.repeat(z, hq // hkv, axis=1) for z in (k, v))
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * hd)
    return (o * jax.nn.sigmoid(qkvg[:, v_end:])) @ _f32(W["o"])


def expert_layer(cfg, W, m, *, first: int = 0, shared: bool = True,
                 picks=None):
    """One expert sublayer's output BEFORE its post norm on the normed
    input ``m`` (T, hidden), for the slice of experts ``W["e_w*"]`` holds:
    the router's ``[first, first + n_held)``.  Returns ``(f, picked,
    gap)``."""
    k = cfg.num_experts_per_tok
    sigma = jax.nn.sigmoid(m @ _f32(W["gate"]))
    biased = sigma + _f32(W["gate_bias"])
    top = jnp.sort(biased, axis=1)[:, ::-1]
    if picks is None:
        picked = jnp.argsort(-biased, axis=1)[:, :k]
        gap = top[:, k - 1] - top[:, k]
    else:
        picked = jnp.asarray(picks)
        worst = jnp.take_along_axis(biased, picked, 1).min(axis=1)
        gap = jnp.maximum(top[:, k - 1] - worst, 0.0)
    w = jnp.take_along_axis(sigma, picked, 1)
    if cfg.route_norm:
        w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
    w = w * cfg.route_scale
    f = jnp.zeros_like(m)
    if shared and cfg.num_shared_experts:
        f = _swiglu(m, _f32(W["s_w1"]), _f32(W["s_w3"]), _f32(W["s_w2"]))
    for e in range(W["e_w1"].shape[0]):
        # masked weight: zero where token t did not pick expert first + e
        w_e = jnp.sum(jnp.where(picked == first + e, w, 0.0), axis=1)
        f = f + w_e[:, None] * _swiglu(
            m, _f32(W["e_w1"][e]), _f32(W["e_w3"][e]), _f32(W["e_w2"][e]))
    return f, picked, gap


def layer_weights(params: dict, i: int) -> dict:
    pre = f"L{i}."
    return {n[len(pre):]: v for n, v in params.items() if n.startswith(pre)}


def reference_forward(cfg, params: dict, history, picks=None) -> dict:
    """``history``: item indices, oldest first.  Returns ``logits`` (vocab,)
    at the last position, ``h_last`` (hidden,) — the final-normed state the
    head multiplies — ``x_last`` and ``x0_last`` (the residual stream at the
    last position after the layers and before them: their difference is
    what the layers added), ``picks`` (L_moe, T, top_k), and per sparse
    layer and token ``margin`` (unforced) or ``violation`` (forced), see
    above."""
    tokens = np.asarray(history, np.int64)
    eps = cfg.rms_norm_eps
    out_picks, gaps = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[tokens]
        if cfg.mup_enabled:
            x = x * np.sqrt(cfg.hidden_size).astype(np.float32)
        x0_last = x[-1]
        for i, kind in enumerate(cfg.layer_types):
            W = layer_weights(params, i)
            x = x + _rms(attention_layer(cfg, W, x, kind),
                         _f32(W["post_attn_norm"]), eps)
            m = _rms(x, _f32(W["pre_mlp_norm"]), eps)
            if i < cfg.num_dense_layers:
                f = _swiglu(m, _f32(W["w1"]), _f32(W["w3"]), _f32(W["w2"]))
            else:
                f, picked, gap = expert_layer(
                    cfg, W, m, first=cfg.first_expert_held,
                    picks=None if picks is None else picks[len(out_picks)])
                out_picks.append(picked)
                gaps.append(gap)
            x = x + _rms(f, _f32(W["post_mlp_norm"]), eps)
        h_last = _rms(x[-1], _f32(params["final_norm"]), eps)
        logits = _f32(params["head"])[:cfg.vocab_size] @ h_last
    res = {"logits": logits, "h_last": h_last, "x_last": x[-1],
           "x0_last": x0_last,
           "picks": (jnp.stack(out_picks) if out_picks else None)}
    res["violation" if picks is not None else "margin"] = (
        jnp.stack(gaps) if gaps else None)
    return res
