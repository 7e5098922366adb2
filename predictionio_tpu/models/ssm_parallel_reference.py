"""The plain reference of ``models/ssm_parallel.py``: the same layer
equations in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, ONE history at a time, the state-space recurrence token by token
(``lax.scan`` over the state), the full ``(T, T)`` attention matrix; no
packing, no chunking, no kernels, nothing imported from the serving program.

Reads the program's parameter dict (``S.<name>``: every layer's tensor
stacked on a leading axis; ``embed``, ``head``, ``final_norm``).

What is set by convention, because the published ``config.json`` does not
say (the configuration file lists each under ``assumed``): the block's order
and where each multiplier acts are the public ``falcon_h1`` implementation's
as remembered — both mixers read ONE normed input and their outputs are
summed into the residual stream; ``ssm_multipliers`` scale the five
segments ``[z | x | B | C | dt]`` of the input projection's OUTPUT;
``key_multiplier`` scales k alone; rotary embedding (half rotation) on every
layer; the gated norm normalises each of the ``mamba_n_groups`` groups of
``y * SiLU(z)`` on its own; ``softplus`` without a clamp on ``dt``; no bias
but the convolution's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, bias):
    """Causal depthwise convolution, zeros before the first event:
    ``y_t = sum_j w[j] x_(t-W+1+j) + bias``.  ``x`` (T, C), ``w`` (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[j:j + x.shape[0]] * w[j] for j in range(width)) + bias


def _rope_half(x, theta):
    """``[x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]`` at positions
    0..T-1, angle ``pos * theta^(-2i/d)``; ``x`` (T, heads, d)."""
    t, _, d = x.shape
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(t, dtype=np.float64)[:, None] * inv[None, :]).astype(
        np.float32)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ssd_recurrence(x, b, c, dt, a, d, h0=None):
    """Token by token: ``h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t``,
    ``y_t = h_t C_t + D x_t``.  ``x`` (T, H, P); ``b``/``c`` (T, G, N), group
    ``g`` read by heads ``[g H/G, (g+1) H/G)``; ``dt`` (T, H); ``a``/``d``
    (H,).  Returns ``y`` (T, H, P) and the last state (H, P, N), all f32."""
    x, b, c, dt, a, d = (_f32(v) for v in (x, b, c, dt, a, d))
    _, heads, p = x.shape
    per_group = heads // b.shape[1]
    h = (jnp.zeros((heads, p, b.shape[2]), jnp.float32) if h0 is None
         else _f32(h0))

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        b_h = jnp.repeat(b_t, per_group, axis=0)  # (H, N): the head's group
        c_h = jnp.repeat(c_t, per_group, axis=0)
        h = (h * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_h) + d[:, None] * x_t

    with jax.default_matmul_precision("highest"):
        h, y = jax.lax.scan(step, h, (x, b, c, dt))
    return y, h


def mup_vector(cfg):
    """``ssm_multipliers`` over the segments ``[z | x | B | C | dt]`` of the
    input projection's outputs."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return np.concatenate([
        np.full(w, m, np.float32)
        for w, m in zip(widths, cfg.ssm_multipliers)])


def ssm_branch(cfg, W, a):
    """The state-space mixer on the normed input ``a`` (T, hidden); ``W``
    ONE layer's tensors."""
    t = a.shape[0]
    heads, p, g, n = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                      cfg.mamba_d_state)
    ds = cfg.mamba_d_ssm
    proj = ((cfg.ssm_in_multiplier * a) @ _f32(W["ssm_in"])) * mup_vector(cfg)
    z, xbc, dt = (proj[:, :ds], proj[:, ds:2 * ds + 2 * g * n],
                  proj[:, 2 * ds + 2 * g * n:])
    xbc = jax.nn.silu(_conv(xbc, _f32(W["conv"]), _f32(W["conv_bias"])))
    dt = jax.nn.softplus(dt + _f32(W["dt_bias"]))
    y, _ = ssd_recurrence(
        xbc[:, :ds].reshape(t, heads, p),
        xbc[:, ds:ds + g * n].reshape(t, g, n),
        xbc[:, ds + g * n:].reshape(t, g, n),
        dt, -jnp.exp(_f32(W["A_log"])), W["D"])
    y = y.reshape(t, ds) * jax.nn.silu(z)
    # the gated norm: each group of d_ssm / n_groups channels on its own
    y = _rms(y.reshape(t, g, ds // g), _f32(W["gate_norm"]).reshape(g, -1),
             cfg.rms_norm_eps).reshape(t, ds)
    return cfg.ssm_out_multiplier * (y @ _f32(W["ssm_out"]))


def attention_branch(cfg, W, a):
    """Grouped-query causal softmax attention on the SAME normed input."""
    t = a.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    qkv = (cfg.attention_in_multiplier * a) @ _f32(W["qkv"])
    q = qkv[:, :hq * hd].reshape(t, hq, hd)
    k = cfg.key_multiplier * qkv[:, hq * hd:(hq + hkv) * hd].reshape(
        t, hkv, hd)
    v = qkv[:, (hq + hkv) * hd:].reshape(t, hkv, hd)
    q, k = _rope_half(q, cfg.rope_theta), _rope_half(k, cfg.rope_theta)
    kv_of = np.arange(hq) // (hq // hkv)  # the key/value head of each query head
    s = jnp.einsum("thd,shd->hts", q, k[:, kv_of]) / np.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", pr, v[:, kv_of]).reshape(t, hq * hd)
    return cfg.attention_out_multiplier * (o @ _f32(W["o"]))


def mlp_branch(cfg, W, f):
    gate_m, down_m = cfg.mlp_multipliers
    return down_m * ((jax.nn.silu(gate_m * (f @ _f32(W["w1"])))
                      * (f @ _f32(W["w3"]))) @ _f32(W["w2"]))


def layer(cfg, W, x):
    """One block: both mixers on one normed input, summed into the stream;
    then the feed-forward.  Returns the stream and the two mixers' parts."""
    a = _rms(x, _f32(W["in_norm"]), cfg.rms_norm_eps)
    m_s, m_a = ssm_branch(cfg, W, a), attention_branch(cfg, W, a)
    x = x + m_s + m_a
    f = _rms(x, _f32(W["ffn_norm"]), cfg.rms_norm_eps)
    return x + mlp_branch(cfg, W, f), m_s, m_a


def layer_weights(params: dict, i: int) -> dict:
    return {name[2:]: v[i] for name, v in params.items()
            if name.startswith("S.")}


def reference_forward(cfg, params: dict, history) -> dict:
    """``history``: item indices, oldest first.  Returns ``logits``
    (vocab,) at the last position, ``h_last`` (hidden,) — the final-normed
    state times ``lm_head_multiplier``, what the head multiplies — and
    ``x_last``, the residual stream it is the norm of."""
    tokens = np.asarray(history, np.int64)
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * _f32(params["embed"])[tokens]
        for i in range(cfg.num_hidden_layers):
            x, _, _ = layer(cfg, layer_weights(params, i), x)
        h_last = cfg.lm_head_multiplier * _rms(
            x[-1], _f32(params["final_norm"]), cfg.rms_norm_eps)
        logits = _f32(params["head"])[:cfg.vocab_size] @ h_last
    return {"logits": logits, "h_last": h_last, "x_last": x[-1]}
