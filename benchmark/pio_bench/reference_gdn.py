"""The benchmark's own plain reference of the gated-delta-rule /
full-attention hybrid sequence model, and the trunk comparison that decides
``correct`` for its cells.  Imports nothing from the program under test.

The layer equations (``configs/olmo-hybrid-7b-l16.json`` gives the keys and,
under ``assumed``, what the published config leaves open).  Every layer is
two post-normed residual sublayers, ``x + RMSNorm(mixer(x))`` then ``x +
RMSNorm(SwiGLU(x))``.  A *linear* layer's mixer: ``[q~ | k~ | v~] = x W_qkv``,
each channel through a causal depthwise convolution of width 4 (zeros
before the first event, no bias) and SiLU; heads x ``q, k`` in R^dk, ``v``
in R^dv; ``q <- q/|q| dk^-1/2``, ``k <- k/|k|``; per head ``beta = 2
sigmoid(x W_b)``, ``g = -exp(A_log) softplus(x W_a + dt_bias)``; from ``S =
0``: ``S_t = e^g S_(t-1) + beta k (v - e^g S_(t-1)^T k)^T``, ``o_t = S_t^T
q``; output ``[RMSNorm_dv(o) * SiLU(x W_g)] W_o``.  A *full* layer's mixer:
``[q | k | v] = x W_qkv``, q and k RMS-normed over the whole projection,
heads x 128, causal softmax attention with no rotary embedding, ``W_o``.
Final RMSNorm.

Everything is float32 with matmuls at ``highest``; one history at a time;
the recurrence token by token (``lax.scan`` over the state: no chunking);
the full ``(T, T)`` attention matrix; one LAYER a compiled call, so that one
layer's bf16 weights are upcast at a time and the reference fits beside the
resident model.  What departs from "plain": a history is padded at its END
to a bucket length (one compile per bucket, not per length); every mixer
here is causal, so the real positions are blind to the padding, and nothing
is read from padded positions.

The parameter dict is the program's: ``embed``, ``final_norm``, and
``S<j>.<name>`` holding slot ``j`` of every period of the layer pattern,
stacked on a leading axis (``qkv``, ``conv``, ``ab`` = ``[W_a | W_b]``,
``A_log``, ``dt_bias``, ``gate``, ``o_norm``, ``o`` for a linear slot;
``qkv``, ``q_norm``, ``k_norm``, ``o`` for a full one; ``attn_norm``,
``ffn_norm``, ``w1``, ``w3``, ``w2`` for both).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (64, 128, 256, 512, 1024, 2048)
LINEAR = "linear_attention"


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _linear_mixer(W, x, hf, normalize_qk):
    t = x.shape[0]
    h, dk, dv = (hf["linear_num_value_heads"], hf["linear_key_head_dim"],
                 hf["linear_value_head_dim"])
    width = hf["linear_conv_kernel_dim"]
    pre = jnp.pad(x @ _f32(W["qkv"]), ((width - 1, 0), (0, 0)))
    taps = _f32(W["conv"])
    qkv = jax.nn.silu(sum(pre[j:j + t] * taps[j] for j in range(width)))
    q = qkv[:, :h * dk].reshape(t, h, dk)
    k = qkv[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = qkv[:, 2 * h * dk:].reshape(t, h, dv)
    if normalize_qk:
        q, k = _unit(q), _unit(k)
    q = q * dk ** -0.5
    ab = x @ _f32(W["ab"])
    beta = jax.nn.sigmoid(ab[:, h:]) * (
        2.0 if hf["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(W["A_log"]) * jax.nn.softplus(ab[:, :h] + W["dt_bias"])

    def step(s, xs):  # s (h, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        err = v_t - jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * err)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, W["o_norm"], hf["rms_norm_eps"])
    gate = jax.nn.silu(x @ _f32(W["gate"])).reshape(t, h, dv)
    return (o * gate).reshape(t, h * dv) @ _f32(W["o"])


def _full_mixer(W, x, hf):
    t, d = x.shape
    h = hf["num_attention_heads"]
    qkv = x @ _f32(W["qkv"])
    q = _rms(qkv[:, :d], W["q_norm"], hf["rms_norm_eps"]).reshape(t, h, -1)
    k = _rms(qkv[:, d:2 * d], W["k_norm"], hf["rms_norm_eps"]).reshape(
        t, h, -1)
    v = qkv[:, 2 * d:].reshape(t, h, -1)
    s = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(d // h)
    causal = np.tril(np.ones((t, t), bool))
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", a, v).reshape(t, d) @ _f32(W["o"])


@functools.partial(jax.jit, static_argnames=("kind", "hf_items",
                                             "normalize_qk"))
def _layer(W, x, kind, hf_items, normalize_qk):
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        y = (_linear_mixer(W, x, hf, normalize_qk) if kind == LINEAR
             else _full_mixer(W, x, hf))
        x = x + _rms(y, W["attn_norm"], eps)
        y = (jax.nn.silu(x @ _f32(W["w1"])) * (x @ _f32(W["w3"]))) @ _f32(
            W["w2"])
        return x + _rms(y, W["ffn_norm"], eps)


def bucket_for(n: int) -> int:
    return next(b for b in BUCKETS if b >= n)


def period_of(layer_types) -> tuple:
    kinds = tuple(layer_types)
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]
    return kinds


def forward(hf: dict, params: dict, history, normalize_qk: bool = True):
    """``history`` item indices, oldest first.  Returns ``h_last`` (hidden,)
    float32 NumPy: the final-normed state the head multiplies."""
    n = len(history)
    tokens = np.zeros(bucket_for(n), np.int32)
    tokens[:n] = history
    period = period_of(hf["layer_types"])
    keys = tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool))))
    x = _f32(params["embed"][tokens])
    for i in range(hf["num_hidden_layers"]):
        at, j = divmod(i, len(period))
        pre = f"S{j}."
        W = {name[len(pre):]: params[name][at] for name in params
             if name.startswith(pre)}
        x = _layer(W, x, period[j], keys, normalize_qk)
    h_last = _rms(x[n - 1], params["final_norm"], hf["rms_norm_eps"])
    return np.asarray(jax.device_get(h_last), np.float32)


def rel_errors(rows: list, wants: list) -> dict:
    """The program's ``h_last`` of each row against the reference's: the
    number that is judged is the worst relative L2 error; the mean is
    reported beside it.  An error that is not a number counts as 1e9."""
    errs = []
    for row, want in zip(rows, wants):
        want = np.asarray(want, np.float64)
        got = np.asarray(row["h_last"], np.float64)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        errs.append(err if np.isfinite(err) else 1e9)
    worst = int(np.argmax(errs)) if errs else -1
    return {"rows": len(rows), "h_last_rel_err": max(errs, default=0.0),
            "h_last_rel_err_mean": float(np.mean(errs)) if errs else 0.0,
            "worst_row_tokens": len(rows[worst]["history"]) if errs else 0}


def compare_trunk(hf: dict, params: dict, rows: list,
                  normalize_qk: bool = True) -> dict:
    """``rows``: dicts with ``history`` and the program's ``h_last`` (what
    its head scored), each against this reference's own forward pass."""
    return rel_errors(rows, [
        forward(hf, params, row["history"], normalize_qk) for row in rows])
