"""The plain reference of ``models/latent_moe.py``: the same layer
equations in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, ONE history at a time, a Python loop over the experts
with masked weights, the full ``(T, T)`` attention matrix; no packing, no
kernels, nothing imported from the serving program.

Departures from the published description, each deliberate:

* the multi-token-prediction module is left out (a training-time head);
* RoPE is applied to interleaved pairs directly (as a complex rotation of
  ``x[2i] + i x[2i+1]``); the published code first de-interleaves and then
  rotates halves, which permutes q_rope and k_rope alike and leaves every
  q·k product unchanged;
* ``picks`` may be FORCED (the experts a program under test selected): the
  weights are then still this reference's own unbiased scores of those
  experts.  Under bf16 a near-tie between the 8th and 9th score flips an
  expert, which changes the output by far more than rounding does; forcing
  separates "the program routed admissibly" (``violation``: how far below
  this reference's own 8th-best score+bias the worst forced pick lies) from
  "given that routing, the numbers agree".  Unforced, ``margin`` is the gap
  between the 8th and 9th score+bias, which identifies the near-ties.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Interleaved-pair rotation of ``x`` (..., T, d) at positions 0..T-1."""
    t, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(
        1j * ang).astype(jnp.complex64)
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def reference_forward(cfg, params: dict, history, picks=None) -> dict:
    """``history``: item indices, oldest first.  Returns ``logits`` (vocab,)
    at the last position, ``h_last`` (hidden,) — the final-normed state the
    head multiplies — ``picks`` (L_moe, T, top_k), and per sparse layer and
    token ``margin`` (unforced) or ``violation`` (forced), see above."""
    P = params
    tokens = np.asarray(history, np.int64)
    t = len(tokens)
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    eps, k = cfg.rms_norm_eps, cfg.num_experts_per_tok
    causal = np.tril(np.ones((t, t), bool))
    out_picks, gaps = [], []
    with jax.default_matmul_precision("highest"):
        x = _f32(P["embed"])[tokens]
        for i in range(cfg.num_hidden_layers):
            p = f"L{i}."
            xn = _rms(x, _f32(P[p + "attn_norm"]), eps)
            c_q = _rms(xn @ _f32(P[p + "q_a"]), _f32(P[p + "q_a_norm"]), eps)
            q = (c_q @ _f32(P[p + "q_b"])).reshape(t, h, dn + dr)
            kv = xn @ _f32(P[p + "kv_a"])
            c_kv = _rms(kv[:, :cfg.kv_lora_rank], _f32(P[p + "kv_a_norm"]),
                        eps)
            k_r = _rope(kv[:, cfg.kv_lora_rank:], cfg.rope_theta)  # (T, dr)
            kvb = (c_kv @ _f32(P[p + "kv_b"])).reshape(t, h, dn + dv)
            q_r = _rope(q[..., dn:].transpose(1, 0, 2), cfg.rope_theta)
            s = (jnp.einsum("thd,shd->hts", q[..., :dn], kvb[..., :dn])
                 + jnp.einsum("htd,sd->hts", q_r, k_r)) / np.sqrt(dn + dr)
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hts,shd->thd", a, kvb[..., dn:]).reshape(t, h * dv)
            x = x + o @ _f32(P[p + "o"])
            xn = _rms(x, _f32(P[p + "ffn_norm"]), eps)
            if i < cfg.first_k_dense_replace:
                x = x + _swiglu(xn, _f32(P[p + "w1"]), _f32(P[p + "w3"]),
                                _f32(P[p + "w2"]))
                continue
            sigma = jax.nn.sigmoid(xn @ _f32(P[p + "gate"]))
            biased = sigma + _f32(P[p + "gate_bias"])
            top = jnp.sort(biased, axis=1)[:, ::-1]
            if picks is None:
                picked = jnp.argsort(-biased, axis=1)[:, :k]
                gaps.append(top[:, k - 1] - top[:, k])
            else:
                picked = jnp.asarray(picks[len(out_picks)])
                worst = jnp.take_along_axis(biased, picked, 1).min(axis=1)
                gaps.append(jnp.maximum(top[:, k - 1] - worst, 0.0))
            out_picks.append(picked)
            w = jnp.take_along_axis(sigma, picked, 1)
            if cfg.norm_topk_prob:
                w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
            w = w * cfg.routed_scaling_factor
            y = _swiglu(xn, _f32(P[p + "s_w1"]), _f32(P[p + "s_w3"]),
                        _f32(P[p + "s_w2"]))
            for e in range(cfg.n_routed_experts):
                # masked weight: zero where token t did not pick expert e
                w_e = jnp.sum(jnp.where(picked == e, w, 0.0), axis=1)
                y = y + w_e[:, None] * _swiglu(
                    xn, _f32(P[p + "e_w1"][e]), _f32(P[p + "e_w3"][e]),
                    _f32(P[p + "e_w2"][e]))
            x = x + y
        h_last = _rms(x[-1], _f32(P["final_norm"]), eps)
        logits = _f32(P["head"])[:cfg.vocab_size] @ h_last
    res = {"logits": logits, "h_last": h_last,
           "picks": (jnp.stack(out_picks) if out_picks else None)}
    res["violation" if picks is not None else "margin"] = (
        jnp.stack(gaps) if gaps else None)
    return res
