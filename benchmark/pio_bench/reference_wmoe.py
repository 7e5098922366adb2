"""The benchmark's own plain reference of the window/global-attention
sparse-expert sequence model, and the trunk comparison that decides
``correct`` for its cells.  Imports nothing from the program under test.

The layer equations (``configs/trinity-large-l5-ep8.json`` gives the keys;
the public ``afmoe`` ones): ``x0 = E_in[token] * sqrt(hidden)``; per layer,
sandwich-normed residual blocks, RMSNorm(eps) with a learned scale:

* attention: ``a = Norm(x)``; ``[q | k | v | g] = a W_qkvg`` (48 query heads,
  8 key/value heads, the output gate as wide as the query heads); q and k
  RMS-normed over each head's 128; a ``sliding_attention`` layer rotates q
  and k by halves (theta 10,000, position within the history) and sees keys
  ``t - 4096 < s <= t``; a ``full_attention`` layer has NO rotary embedding
  and sees ``s <= t``; query head ``h`` reads key/value head ``h // 6``; ``x
  + Norm(((softmax(q.k / sqrt(128)) v) * sigmoid(g)) W_o)``;
* feed-forward: ``m = Norm(x)``; the leading layers a dense SwiGLU; the
  rest ``SwiGLU_shared(m) + sum_j w_j SwiGLU_{e_j}(m)``, 4 of the router's
  256 picked by ``sigmoid(m W_r) + bias``, weighed by the unbiased scores,
  normalised, x 2.448; ``x + Norm(f)``.

Everything is float32 with matmuls at ``highest``; one history at a time;
the whole ``(T, T)`` score matrix of a layer, computed a block of queries
at a time so that a 16,384-event history fits beside the resident model; a
loop over the HELD experts with masked weights (``lax.fori_loop``: one
expert's bf16 weights upcast at a time).  What departs from "plain":

* **held experts**: the parameters hold the router's experts
  ``[first_expert_held, first_expert_held + n_held)`` of every expert
  layer, this chip's share of the deployment.  A pick outside the slice
  adds nothing, here as in the program: the reference is given the same
  experts.  Routing and the weights' normalisation are over all 256;
* a history is padded at its END to a bucket length (one compile per
  bucket); causal attention keeps the real positions blind to the padding;
* routing is FORCED to the experts the program picked, as
  ``reference_seq`` does and for its reason: ``violation`` is how far below
  this reference's own 4th-best ``sigma + bias`` the program's worst pick
  lies, on this reference's own trajectory; ``flipped`` counts the (token,
  layer) decisions that differ, reported and not judged.

The CONTROLS are this reference computed wrongly on purpose, each a
mechanism the program could get wrong with well-formed answers:
``drop_window`` (a window layer sees its whole history), ``rope_on_global``
(the global layers rotate too), ``kv_modulo`` (query head ``h`` reads
key/value head ``h % 8``).  The cell's comparison must tell each from the
sound program (``check_window.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (64, 1024, 4096, 16384)
# queries of one block of the score matrix, tokens of one block of a dense
# feed-forward: (48 heads x 128 x 16,384) f32 scores are 403 MB
QUERY_BLOCK, TOKEN_BLOCK = 128, 2048
# the keys of the model's shape `forward` reads from `hf`
KEYS = ("hidden_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "sliding_window", "layer_types", "rope_theta", "rms_norm_eps",
        "num_experts_per_tok", "route_norm", "route_scale", "mup_enabled",
        "num_shared_experts", "first_expert_held")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _blocks(fn, x, size):
    """``fn`` over ``x``'s leading axis, ``size`` rows at a time."""
    n = x.shape[0]
    if n <= size:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(n // size, size, *x.shape[1:]))
    return out.reshape(n, *out.shape[2:])


def _rope_half(x, theta):
    """``[x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]`` at positions
    0..T-1, angle ``pos * theta^(-2i/d)``; ``x`` (T, heads, d)."""
    t, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(t, dtype=np.float64)[:, None] * inv[None, :]).astype(
        np.float32)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf, P, p, kind, x, controls):
    t = x.shape[0]
    hq, hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    eps, window = hf["rms_norm_eps"], hf["sliding_window"]
    a = _rms(x, P[p + "in_norm"], eps)
    qkvg = a @ _f32(P[p + "qkvg"])
    q_end, k_end, v_end = hq * hd, (hq + hkv) * hd, (hq + 2 * hkv) * hd
    q = _rms(qkvg[:, :q_end].reshape(t, hq, hd), P[p + "q_norm"], eps)
    k = _rms(qkvg[:, q_end:k_end].reshape(t, hkv, hd), P[p + "k_norm"], eps)
    v = qkvg[:, k_end:v_end].reshape(t, hkv, hd)
    windowed = kind == "sliding_attention"
    if windowed or "rope_on_global" in controls:
        theta = float(hf["rope_theta"])
        q, k = _rope_half(q, theta), _rope_half(k, theta)
    # the key/value head of each query head
    heads = np.arange(hq)
    kv_of = heads % hkv if "kv_modulo" in controls else heads // (hq // hkv)
    k, v = k[:, kv_of], v[:, kv_of]
    cols = np.arange(t)[None, :]

    def one_block(args):
        qb, rows = args  # (B, hq, hd), (B,)
        see = cols <= rows[:, None]
        if windowed and "drop_window" not in controls:
            see = see & (cols > rows[:, None] - window)
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v)

    size = min(t, QUERY_BLOCK)
    o = jax.lax.map(one_block, (q.reshape(t // size, size, hq, hd),
                                jnp.arange(t).reshape(t // size, size)))
    o = o.reshape(t, hq * hd) * jax.nn.sigmoid(qkvg[:, v_end:])
    return _rms(o @ _f32(P[p + "o"]), P[p + "post_attn_norm"], eps)


@functools.partial(jax.jit, static_argnames=("hf_items", "controls"))
def _forward(P, tokens, n_real, picks, hf_items, controls=()):
    hf = dict(hf_items)
    t = tokens.shape[0]
    eps, k = hf["rms_norm_eps"], hf["num_experts_per_tok"]
    first, n_dense = hf["first_expert_held"], hf["num_dense_layers"]
    real = jnp.arange(t) < n_real
    worst_violation = jnp.float32(0)
    flipped = jnp.int32(0)
    flipped_last = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        x = _f32(P["embed"][tokens])
        if hf["mup_enabled"]:
            x = x * np.float32(np.sqrt(hf["hidden_size"]))
        x0_last = x[n_real - 1]
        for i, kind in enumerate(hf["layer_types"]):
            p = f"L{i}."
            x = x + _attention(hf, P, p, kind, x, controls)
            m = _rms(x, P[p + "pre_mlp_norm"], eps)
            if i < n_dense:
                f = _blocks(
                    lambda mb, p=p: _swiglu(mb, _f32(P[p + "w1"]),
                                            _f32(P[p + "w3"]),
                                            _f32(P[p + "w2"])),
                    m, TOKEN_BLOCK)
                x = x + _rms(f, P[p + "post_mlp_norm"], eps)
                continue
            sigma = jax.nn.sigmoid(m @ P[p + "gate"])
            biased = sigma + P[p + "gate_bias"]
            own_vals, own = jax.lax.top_k(biased, k)
            picked = picks[i - n_dense]
            worst = jnp.take_along_axis(biased, picked, 1).min(axis=1)
            viol = jnp.where(real, jnp.maximum(own_vals[:, k - 1] - worst,
                                               0.0), 0.0)
            worst_violation = jnp.maximum(worst_violation, viol.max())
            differs = (jnp.sort(own, 1) != jnp.sort(picked, 1)).any(1) & real
            flipped += differs.sum()
            flipped_last += differs[n_real - 1].astype(jnp.int32)
            w = jnp.take_along_axis(sigma, picked, 1)
            if hf["route_norm"]:
                w = w / (w.sum(axis=1, keepdims=True) + 1e-20)
            w = w * hf["route_scale"]
            f = jnp.zeros_like(m)
            if hf["num_shared_experts"]:
                f = _swiglu(m, _f32(P[p + "s_w1"]), _f32(P[p + "s_w3"]),
                            _f32(P[p + "s_w2"]))

            def one_expert(e, f, p=p, picked=picked, w=w, m=m):
                # masked weight: zero where token t did not pick the e-th
                # HELD expert, the router's expert first + e
                w_e = jnp.sum(jnp.where(picked == first + e, w, 0.0), axis=1)
                take = lambda name: _f32(jax.lax.dynamic_index_in_dim(
                    P[p + name], e, keepdims=False))
                return f + w_e[:, None] * _swiglu(
                    m, take("e_w1"), take("e_w3"), take("e_w2"))

            f = jax.lax.fori_loop(0, P[p + "e_w1"].shape[0], one_expert, f)
            x = x + _rms(f, P[p + "post_mlp_norm"], eps)
        h_last = _rms(x[n_real - 1], P["final_norm"], eps)
    return (h_last, x[n_real - 1], x0_last, worst_violation, flipped,
            flipped_last)


def bucket_for(n: int) -> int:
    return next(b for b in BUCKETS if b >= n)


def forward(hf: dict, params: dict, history, picks, controls=()) -> dict:
    """``history`` item indices, oldest first; ``picks`` (sparse layers,
    len(history), top_k) the program's choices.  Returns ``h_last``
    (hidden,) float32 NumPy, ``x_last`` (the residual stream h_last is the
    norm of) and ``added`` (``x_last`` less the scaled embedding it started
    from: what the layers added), ``violation``, ``flipped`` and
    ``flipped_last``."""
    n = len(history)
    t = bucket_for(n)
    tokens = np.zeros(t, np.int32)
    tokens[:n] = history
    padded = np.zeros((picks.shape[0], t, picks.shape[2]), np.int32)
    padded[:, :n] = picks
    items = tuple(sorted(
        (k, tuple(hf[k]) if k == "layer_types" else hf[k]) for k in KEYS))
    h_last, x_last, x0_last, viol, flipped, flipped_last = jax.device_get(
        _forward(params, tokens, np.int32(n), padded, items,
                 tuple(sorted(controls))))
    return {"h_last": np.asarray(h_last, np.float32),
            "x_last": np.asarray(x_last, np.float64),
            "added": np.asarray(x_last, np.float64) - np.asarray(
                x0_last, np.float64),
            "violation": float(viol), "flipped": int(flipped),
            "flipped_last": int(flipped_last), "decisions": n * picks.shape[0]}


def compare_trunk(hf: dict, params: dict, rows: list, controls=()) -> dict:
    """``rows``: dicts with ``history``, ``picks`` and the program's
    ``h_last`` (what its head scored) and ``x_last`` (the f32 residual
    stream at the last position).  The three numbers that are judged:

    * ``added_rel_err``: the error of ``x_last`` over the norm of what the
      LAYERS ADDED to the residual stream (``x_last`` less the embedding
      times ``sqrt(hidden)`` it started from).  The embedding is 55 times a
      sublayer's normed output, so relative to ``h_last`` itself five
      layers' whole contribution is 6 % and an error inside one of them
      drowns in the bf16 rounding of ``h_last``'s own elements (0.11 %);
    * ``h_last_rel_err``: the relative L2 error of ``h_last``, which ties
      what the head scored to that residual stream (the final norm);
    * ``route_violation``: the worst routing violation;

    and the flips that are reported."""
    worst_err = worst_added = worst_viol = 0.0
    worst_tokens = flipped = decisions = rows_flipped = rows_flipped_last = 0

    def rel(got, want, over):
        err = float(np.linalg.norm(got - want) / np.linalg.norm(over))
        return err if np.isfinite(err) else 1e9

    for row in rows:
        ref = forward(hf, params, row["history"], row["picks"], controls)
        want = ref["h_last"].astype(np.float64)
        worst_err = max(worst_err, rel(
            np.asarray(row["h_last"], np.float64), want, want))
        added = rel(np.asarray(row["x_last"], np.float64), ref["x_last"],
                    ref["added"])
        if added > worst_added:
            worst_added, worst_tokens = added, len(row["history"])
        worst_viol = max(worst_viol, ref["violation"])
        flipped += ref["flipped"]
        decisions += ref["decisions"]
        rows_flipped += ref["flipped"] > 0
        rows_flipped_last += ref["flipped_last"] > 0
    return {"rows": len(rows), "added_rel_err": worst_added,
            "worst_row_tokens": worst_tokens, "h_last_rel_err": worst_err,
            "route_violation": worst_viol, "decisions": decisions,
            "flipped_decisions": flipped, "rows_with_a_flip": rows_flipped,
            "rows_with_a_flip_at_the_last_position": rows_flipped_last}
