"""The plain reference of ``models/ssm_moe.py``: the same layer equations in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision,
ONE history at a time, the state-space recurrence token by token
(``ssm_parallel_reference.ssd_recurrence``: ``lax.scan`` over the state, the
other state-space family's plain reference), the full ``(T, T)`` attention
matrix, the router as published (the ten largest logits, a softmax over
them), a loop over the experts; no packing, no chunking, no kernels, nothing
imported from the serving program.

Reads the program's parameter dict (``head``, the tied table; ``final_norm``;
``R<j>.<name>``: every layer of run ``j`` stacked on a leading axis) and the
same ``first_expert_held`` / ``n_held``: the experts in the dict are the
router's ``[first, first + n_held)`` and a pick outside them adds nothing,
here as in the program.

What is set by convention, because the published ``config.json`` names the
parts and not their place (the configuration file lists each under
``assumed``): the block's order and each multiplier's place are the public
``granitemoehybrid`` implementation's as remembered — ``embedding_multiplier``
on the looked-up rows, ``residual_multiplier`` on each sublayer's output as it
enters the stream (the mixer's; the routed experts' and the shared expert's
sum), ``attention_multiplier`` AS the softmax's scale, ``logits_scaling``
dividing the logits; no rotary embedding on any layer; the gated norm over
all ``mamba_d_ssm`` channels at once (``mamba_n_groups`` 1); ``softplus``
without a clamp on ``dt``; the router's softmax over the PICKED logits; no
bias but the convolution's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models.ssm_parallel_reference import (
    _conv, _f32, _rms, ssd_recurrence,
)


def mamba_mixer(cfg, W, a):
    """The state-space mixer on the normed input ``a`` (T, hidden); ``W``
    ONE layer's tensors."""
    t = a.shape[0]
    heads, p, g, n = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                      cfg.mamba_d_state)
    ds = heads * p
    proj = a @ _f32(W["ssm_in"])
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
    y = _rms(y.reshape(t, g, ds // g), _f32(W["gate_norm"]).reshape(g, -1),
             cfg.rms_norm_eps).reshape(t, ds)
    return y @ _f32(W["ssm_out"])


def attention_mixer(cfg, W, a):
    """Grouped-query causal softmax attention, no positions, the scale
    ``attention_multiplier``."""
    t = a.shape[0]
    hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = cfg.hidden_size // hq
    qkv = a @ _f32(W["qkv"])
    q = qkv[:, :hq * hd].reshape(t, hq, hd)
    k = qkv[:, hq * hd:(hq + hkv) * hd].reshape(t, hkv, hd)
    v = qkv[:, (hq + hkv) * hd:].reshape(t, hkv, hd)
    kv_of = np.arange(hq) // (hq // hkv)  # the key/value head of each query head
    s = cfg.attention_multiplier * jnp.einsum("thd,shd->hts", q, k[:, kv_of])
    causal = np.tril(np.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", pr, v[:, kv_of]).reshape(t, hq * hd)
    return o @ _f32(W["o"])


def route(cfg, W, f):
    """The router as published: the ``num_experts_per_tok`` largest logits
    (ties to the lower index) and a softmax over THEM.  ``picked`` (T, k),
    ``weights`` (T, k), the logits (T, experts)."""
    logits = f @ _f32(W["gate"])
    picked = jnp.argsort(-logits, axis=1, stable=True)[
        :, :cfg.num_experts_per_tok]
    top = jnp.take_along_axis(logits, picked, axis=1)
    return picked, jax.nn.softmax(top, axis=1), logits


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def routed_experts(cfg, W, f, picked, weights):
    """``sum_{e in S} g_e SwiGLU_e(f)`` over the picks that are HELD: the
    dict's experts are the router's ``[first_expert_held, first_expert_held
    + n_held)``."""
    out = jnp.zeros_like(f)
    for e in range(W["e_w1"].shape[0]):
        g_e = jnp.sum(jnp.where(
            picked == cfg.first_expert_held + e, weights, 0.0), axis=1)
        out = out + g_e[:, None] * _swiglu(
            f, W["e_w1"][e], W["e_w3"][e], W["e_w2"][e])
    return out


def layer(cfg, kind, W, x):
    """One block.  Returns the stream and the three parts added to it
    (before ``residual_multiplier``): the mixer's, the routed experts', the
    shared expert's."""
    rm = cfg.residual_multiplier
    a = _rms(x, _f32(W["in_norm"]), cfg.rms_norm_eps)
    m = (mamba_mixer if kind == "mamba" else attention_mixer)(cfg, W, a)
    x = x + rm * m
    f = _rms(x, _f32(W["ffn_norm"]), cfg.rms_norm_eps)
    picked, weights, _ = route(cfg, W, f)
    routed = routed_experts(cfg, W, f, picked, weights)
    shared = _swiglu(f, W["s_w1"], W["s_w3"], W["s_w2"])
    return x + rm * (routed + shared), (m, routed, shared)


def layer_weights(cfg, params: dict, i: int):
    """Layer ``i``'s kind and tensors out of the runs' stacks."""
    at = 0
    for j, (kind, n) in enumerate(cfg.runs):
        if i < at + n:
            pre = f"R{j}."
            return kind, {name[len(pre):]: v[i - at]
                          for name, v in params.items()
                          if name.startswith(pre)}
        at += n
    raise IndexError(i)


def reference_forward(cfg, params: dict, history) -> dict:
    """``history``: item indices, oldest first.  Returns ``logits``
    (vocab,) at the last position, ``h_last`` (hidden,) — the final-normed
    state over ``logits_scaling``, what the tied table multiplies — and
    ``x_last``, the residual stream it is the norm of."""
    tokens = np.asarray(history, np.int64)
    table = _f32(params["head"])[:cfg.vocab_size]
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * table[tokens]
        for i in range(cfg.num_hidden_layers):
            kind, W = layer_weights(cfg, params, i)
            x, _ = layer(cfg, kind, W, x)
        h_last = _rms(x[-1], _f32(params["final_norm"]),
                      cfg.rms_norm_eps) / cfg.logits_scaling
        logits = table @ h_last
    return {"logits": logits, "h_last": h_last, "x_last": x[-1]}
