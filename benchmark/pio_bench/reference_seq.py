"""The benchmark's own plain reference of the latent-attention
sparse-expert sequence model, and the trunk comparison that decides
``correct`` for its cells.  Imports nothing from the program under test.

The layer equations (``configs/joyai-llm-flash-l5.json`` gives the keys):
pre-norm residual blocks, RMSNorm(eps) with a learned scale; attention
``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads x [nope | rope];
``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, heads x [k_nope | v] =
``c_kv W_kvb``; interleaved RoPE on q_rope and on the one shared k_r;
``softmax((q_nope.k_nope + q_rope.k_r)/sqrt(d_nope + d_rope)).v``, causal;
``W_o``.  Layer 0's feed-forward is a dense SwiGLU, the others ``sum_e w_e
SwiGLU_e(x) + SwiGLU_shared(x)``, 8 of 256 picked by ``sigmoid(x W_g) +
bias``, weighed by the unbiased scores, normalised, x 2.5.  Final RMSNorm.

Everything is float32 with matmuls at ``highest``; one history at a time;
the full ``(T, T)`` attention matrix; a loop over ALL the experts with
masked weights (``lax.fori_loop``, so that one expert's bf16 weights are
upcast at a time and the reference fits beside the resident model).  What
departs from "plain":

* a history is padded at its END to a bucket length (one compile per
  bucket, not per length); causal attention keeps the real positions blind
  to the padding, and nothing is read from padded positions;
* routing is FORCED to the experts the program picked.  Under bf16 a
  near-tie between the 8th and 9th score flips an expert, and that moves
  the output far more than rounding does.  So the comparison has two
  parts: the program's picks must be ADMISSIBLE — ``violation``, how far
  below this reference's own 8th-best ``sigma + bias`` the program's worst
  pick lies, on this reference's own trajectory — and, given those picks
  (weighed by this reference's own unbiased scores), ``h_last`` must agree.
  ``flipped`` counts the (token, layer) decisions where the program's set
  differs from this reference's own; it is reported, not judged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (128, 256, 512, 1024, 2048)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Interleaved pairs (x[2i], x[2i+1]) rotated by pos * theta^(-2i/d);
    ``x`` (..., T, d), positions 0..T-1."""
    t, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(t, dtype=np.float64)[:, None] * inv[None, :]).astype(
        np.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    cos, sin = np.cos(ang), np.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


@functools.partial(jax.jit, static_argnames=("hf_items",))
def _forward(P, tokens, n_real, picks, hf_items):
    hf = dict(hf_items)
    t = tokens.shape[0]
    h, dn, dr, dv = (hf["num_attention_heads"], hf["qk_nope_head_dim"],
                     hf["qk_rope_head_dim"], hf["v_head_dim"])
    rank, eps, k = hf["kv_lora_rank"], hf["rms_norm_eps"], \
        hf["num_experts_per_tok"]
    n_experts, theta = hf["n_routed_experts"], float(hf["rope_theta"])
    causal = np.tril(np.ones((t, t), bool))
    real = jnp.arange(t) < n_real
    worst_violation = jnp.float32(0)
    flipped = jnp.int32(0)
    flipped_last = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        x = _f32(P["embed"][tokens])
        for i in range(hf["num_hidden_layers"]):
            p = f"L{i}."
            xn = _rms(x, P[p + "attn_norm"], eps)
            c_q = _rms(xn @ _f32(P[p + "q_a"]), P[p + "q_a_norm"], eps)
            q = (c_q @ _f32(P[p + "q_b"])).reshape(t, h, dn + dr)
            kv = xn @ _f32(P[p + "kv_a"])
            c_kv = _rms(kv[:, :rank], P[p + "kv_a_norm"], eps)
            k_r = _rope(kv[:, rank:], theta)
            kvb = (c_kv @ _f32(P[p + "kv_b"])).reshape(t, h, dn + dv)
            q_r = _rope(q[..., dn:].transpose(1, 0, 2), theta)
            s = (jnp.einsum("thd,shd->hts", q[..., :dn], kvb[..., :dn])
                 + jnp.einsum("htd,sd->hts", q_r, k_r)) / np.sqrt(dn + dr)
            a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            o = jnp.einsum("hts,shd->thd", a, kvb[..., dn:]).reshape(
                t, h * dv)
            x = x + o @ _f32(P[p + "o"])
            xn = _rms(x, P[p + "ffn_norm"], eps)
            if i < hf["first_k_dense_replace"]:
                x = x + _swiglu(xn, _f32(P[p + "w1"]), _f32(P[p + "w3"]),
                                _f32(P[p + "w2"]))
                continue
            sigma = jax.nn.sigmoid(xn @ P[p + "gate"])
            biased = sigma + P[p + "gate_bias"]
            own_vals, own = jax.lax.top_k(biased, k)
            picked = picks[i - hf["first_k_dense_replace"]]
            worst = jnp.take_along_axis(biased, picked, 1).min(axis=1)
            viol = jnp.where(real, jnp.maximum(own_vals[:, k - 1] - worst,
                                               0.0), 0.0)
            worst_violation = jnp.maximum(worst_violation, viol.max())
            differs = (jnp.sort(own, 1) != jnp.sort(picked, 1)).any(1) & real
            flipped += differs.sum()
            flipped_last += differs[n_real - 1].astype(jnp.int32)
            w = jnp.take_along_axis(sigma, picked, 1)
            if hf["norm_topk_prob"]:
                w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
            w = w * hf["routed_scaling_factor"]
            y = _swiglu(xn, _f32(P[p + "s_w1"]), _f32(P[p + "s_w3"]),
                        _f32(P[p + "s_w2"]))

            def one_expert(e, y, p=p, picked=picked, w=w, xn=xn):
                w_e = jnp.sum(jnp.where(picked == e, w, 0.0), axis=1)
                take = lambda name: _f32(jax.lax.dynamic_index_in_dim(
                    P[p + name], e, keepdims=False))
                return y + w_e[:, None] * _swiglu(
                    xn, take("e_w1"), take("e_w3"), take("e_w2"))

            x = x + jax.lax.fori_loop(0, n_experts, one_expert, y)
        h_last = _rms(x[n_real - 1], P["final_norm"], eps)
    return h_last, worst_violation, flipped, flipped_last


def bucket_for(n: int) -> int:
    return next(b for b in BUCKETS if b >= n)


def forward(hf: dict, params: dict, history, picks) -> dict:
    """``history`` item indices, oldest first; ``picks`` (sparse layers,
    len(history), top_k) the program's choices.  Returns ``h_last``
    (hidden,) float32 NumPy, ``violation``, ``flipped`` and
    ``flipped_last``."""
    n = len(history)
    t = bucket_for(n)
    tokens = np.zeros(t, np.int32)
    tokens[:n] = history
    padded = np.zeros((picks.shape[0], t, picks.shape[2]), np.int32)
    padded[:, :n] = picks
    keys = tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool))))
    h_last, viol, flipped, flipped_last = jax.device_get(_forward(
        params, tokens, np.int32(n), padded, keys))
    return {"h_last": np.asarray(h_last, np.float32),
            "violation": float(viol), "flipped": int(flipped),
            "flipped_last": int(flipped_last), "decisions": n * picks.shape[0]}


def compare_trunk(hf: dict, params: dict, rows: list) -> dict:
    """``rows``: dicts with ``history``, ``picks`` and the program's
    ``h_last`` (what its head scored).  The two numbers that are judged —
    the worst relative L2 error of ``h_last`` and the worst routing
    violation — and the flips that are reported."""
    worst_err = worst_viol = 0.0
    flipped = decisions = rows_flipped = rows_flipped_last = 0
    for row in rows:
        ref = forward(hf, params, row["history"], row["picks"])
        got = np.asarray(row["h_last"], np.float64)
        want = ref["h_last"].astype(np.float64)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        worst_err = max(worst_err, err)
        worst_viol = max(worst_viol, ref["violation"])
        flipped += ref["flipped"]
        decisions += ref["decisions"]
        rows_flipped += ref["flipped"] > 0
        rows_flipped_last += ref["flipped_last"] > 0
    return {"rows": len(rows), "h_last_rel_err": worst_err,
            "route_violation": worst_viol, "decisions": decisions,
            "flipped_decisions": flipped, "rows_with_a_flip": rows_flipped,
            "rows_with_a_flip_at_the_last_position": rows_flipped_last}
