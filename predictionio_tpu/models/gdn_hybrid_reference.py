"""The plain reference of ``models/gdn_hybrid.py``: the same layer equations
in straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision,
ONE history at a time, the gated delta rule token by token (``lax.scan``
over the state), the full ``(T, T)`` attention matrix; no packing, no
chunking, no kernels, nothing imported from the serving program.

Reads the program's parameter dict (``S<j>.<name>``: the layers at slot
``j`` of every period, stacked on a leading axis; ``embed``, ``head``,
``final_norm``).

What is set by convention, because the published ``config.json`` does not
say (the configuration file lists each under ``assumed``):

* Olmo 2/3 block order: each sublayer's OUTPUT is normed, ``x + Norm(f(x))``;
  in a full layer q and k are RMS-normed over the whole projection before
  the heads split;
* ``rope_theta`` null is read as NO rotary embedding in the full layers (the
  recurrent layers carry order);
* no bias anywhere, the convolution included; the gate's RMSNorm uses the
  config's ``rms_norm_eps``.
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


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_recurrence(q, k, v, g, beta, s0=None):
    """Token by token: ``S_t = a_t S_(t-1) + b_t k_t (v_t - a_t S_(t-1)^T
    k_t)^T``, ``o_t = S_t^T q_t``.  ``q``/``k`` (H, T, dk), ``v`` (H, T, dv),
    ``g``/``beta`` (H, T).  Returns ``o`` (H, T, dv) and the last state
    (H, dk, dv), all f32."""
    q, k, v, g, beta = (_f32(a) for a in (q, k, v, g, beta))
    h, t, dk = q.shape
    s = jnp.zeros((h, dk, v.shape[2]), jnp.float32) if s0 is None else _f32(s0)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        err = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * err)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    with jax.default_matmul_precision("highest"):
        s, o = jax.lax.scan(step, s, (
            q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            g.T, beta.T))
    return o.transpose(1, 0, 2), s


def _conv(x, w):
    """Causal depthwise convolution, zeros before the first event:
    ``y_t = sum_j w[j] x_(t-W+1+j)``.  ``x`` (T, C), ``w`` (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[j] for j in range(width))


def linear_layer(cfg, W, x, *, normalize_qk: bool = True):
    """One gated-delta-rule mixer on ``x`` (T, hidden); ``W`` the slot's
    tensors of ONE layer.  ``normalize_qk`` False is a control."""
    t = x.shape[0]
    h, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    qkv = jax.nn.silu(_conv(x @ _f32(W["qkv"]), _f32(W["conv"])))
    q = qkv[:, :h * dk].reshape(t, h, dk)
    k = qkv[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = qkv[:, 2 * h * dk:].reshape(t, h, dv)
    if normalize_qk:
        q, k = _l2(q), _l2(k)
    q = q * dk ** -0.5
    ab = x @ _f32(W["ab"])
    beta = jax.nn.sigmoid(ab[:, h:])
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(_f32(W["A_log"])) * jax.nn.softplus(
        ab[:, :h] + _f32(W["dt_bias"]))
    o, _ = gated_delta_recurrence(
        q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        g.T, beta.T)
    o = _rms(o.transpose(1, 0, 2), _f32(W["o_norm"]), cfg.rms_norm_eps)
    gate = jax.nn.silu(x @ _f32(W["gate"])).reshape(t, h, dv)
    return (o * gate).reshape(t, h * dv) @ _f32(W["o"])


def full_layer(cfg, W, x):
    """Multi-head causal softmax attention, no rotary embedding."""
    t, d = x.shape
    h = cfg.num_attention_heads
    hd = d // h
    qkv = x @ _f32(W["qkv"])
    q = _rms(qkv[:, :d], _f32(W["q_norm"]), cfg.rms_norm_eps)
    k = _rms(qkv[:, d:2 * d], _f32(W["k_norm"]), cfg.rms_norm_eps)
    v = qkv[:, 2 * d:]
    q, k, v = (a.reshape(t, h, hd) for a in (q, k, v))
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", a, v).reshape(t, d) @ _f32(W["o"])


def reference_forward(cfg, params: dict, history, *,
                      normalize_qk: bool = True) -> dict:
    """``history``: item indices, oldest first.  Returns ``logits``
    (vocab,) at the last position and ``h_last`` (hidden,), the final-normed
    state the head multiplies."""
    P = params
    tokens = np.asarray(history, np.int64)
    eps = cfg.rms_norm_eps
    period = cfg.period
    with jax.default_matmul_precision("highest"):
        x = _f32(P["embed"])[tokens]
        for i in range(cfg.num_hidden_layers):
            n, j = divmod(i, len(period))
            pre = f"S{j}."
            W = {name[len(pre):]: P[name][n] for name in P
                 if name.startswith(pre)}
            if period[j] == "linear_attention":
                y = linear_layer(cfg, W, x, normalize_qk=normalize_qk)
            else:
                y = full_layer(cfg, W, x)
            x = x + _rms(y, _f32(W["attn_norm"]), eps)
            y = _swiglu(x, _f32(W["w1"]), _f32(W["w3"]), _f32(W["w2"]))
            x = x + _rms(y, _f32(W["ffn_norm"]), eps)
        h_last = _rms(x[-1], _f32(P["final_norm"]), eps)
        logits = _f32(P["head"])[:cfg.vocab_size] @ h_last
    return {"logits": logits, "h_last": h_last}
