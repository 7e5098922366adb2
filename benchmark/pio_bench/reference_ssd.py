"""The benchmark's own plain reference of the parallel state-space /
attention sequence model, and the trunk comparison that decides ``correct``
for its cells.  Imports nothing from the program under test.

The layer equations (``configs/falcon-h1-34b-l6.json`` gives the keys and,
under ``assumed``, what the published config leaves open).  ``x0 =
embedding_multiplier * E[token]``; per layer, on ``x`` (T, hidden):

    a   = RMSNorm(x; w_in, eps)
    p   = ((ssm_in_multiplier * a) W_in) * mup      # [z | x | B | C | dt], mup = ssm_multipliers over the five segments
    xBC = SiLU(conv4(p[x|B|C]) + b_conv)            # depthwise causal, zeros before the first event
    dt  = softplus(p[dt] + dt_bias),  A = -exp(A_log)
    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t    # per head, B_t and C_t of the head's GROUP, h = 0 before the first event
    y_t = h_t C_t + D x_t
    m_s = ssm_out_multiplier * (GroupRMSNorm(y * SiLU(z); w_g, groups) W_out)
    q = (attention_in_multiplier * a) W_q,  k = key_multiplier * ((attention_in_multiplier * a) W_k),  v = (attention_in_multiplier * a) W_v
    q, k <- half rotation (theta, position);  o = causal softmax(q k^T / sqrt(head_dim)) v,  query head h reads key/value head h // (heads / kv heads)
    m_a = attention_out_multiplier * (o W_o)
    x   = x + m_s + m_a
    x   = x + mlp_multipliers[1] * ((SiLU(mlp_multipliers[0] * f W_gate) * (f W_up)) W_down),  f = RMSNorm(x; w_ff, eps)

and ``h_last = lm_head_multiplier * RMSNorm(x_last; w_final, eps)``, what
the head multiplies with ``E_out``.

Everything is float32 with matmuls at ``highest``; one history at a time;
the recurrence token by token (``lax.scan`` over the state: no chunking);
the whole ``(T, T)`` score matrix, a block of queries at a time; one LAYER a
compiled call and the feed-forward a block of its columns at a time, so
that one layer's bf16 weights are upcast a part at a time (whole they are
1.72 GB in f32) and the reference fits beside the resident model.  What
departs from "plain": a history is padded at its END to a bucket length
(one compile per bucket, not per length); every mixer here is causal, so the
real positions are blind to the padding, and nothing is read from padded
positions.

The parameter dict is the program's: ``embed``, ``final_norm`` and
``S.<name>``, every layer's tensor stacked on a leading axis (``in_norm``,
``ssm_in``, ``conv``, ``conv_bias``, ``A_log``, ``D``, ``dt_bias``,
``gate_norm``, ``ssm_out``, ``qkv`` = ``[W_q | W_k | W_v]``, ``o``,
``ffn_norm``, ``w1`` (gate), ``w3`` (up), ``w2`` (down)).

The CONTROLS are this reference computed wrongly on purpose, each a
mechanism the program could get wrong with well-formed answers:
``drop_ssm`` and ``drop_attention`` (a branch left out of the sum),
``wrong_group`` (a head reads the OTHER group's B and C), ``no_conv_bias``,
``no_key_multiplier``, ``no_rope``.  The cell's comparison must tell each
from the sound program (``check_ssd.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (128, 512, 2048, 8192)
# queries of one block of the score matrix; column blocks of the feed-forward
QUERY_BLOCK, FFN_BLOCKS = 128, 4
# the keys of the model's shape `forward` reads from `hf`
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_d_ssm", "mamba_n_heads",
        "mamba_d_head", "mamba_n_groups", "mamba_d_state", "rope_theta",
        "rms_norm_eps", "attention_in_multiplier", "attention_out_multiplier",
        "embedding_multiplier", "key_multiplier", "lm_head_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
        "mlp_multipliers")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_half(x, theta):
    t, _, d = x.shape
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(t, dtype=np.float64)[:, None] * inv[None, :]).astype(
        np.float32)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ssm_branch(W, a, hf, controls):
    t = a.shape[0]
    heads, p, g, n, ds = (hf["mamba_n_heads"], hf["mamba_d_head"],
                          hf["mamba_n_groups"], hf["mamba_d_state"],
                          hf["mamba_d_ssm"])
    widths = (ds, ds, g * n, g * n, heads)
    mup = np.concatenate([np.full(w, m, np.float32)
                          for w, m in zip(widths, hf["ssm_multipliers"])])
    proj = ((hf["ssm_in_multiplier"] * a) @ _f32(W["ssm_in"])) * mup
    z, xbc, dt = (proj[:, :ds], proj[:, ds:2 * ds + 2 * g * n],
                  proj[:, 2 * ds + 2 * g * n:])
    taps = _f32(W["conv"])
    width = taps.shape[0]
    pre = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    xbc = sum(pre[j:j + t] * taps[j] for j in range(width))
    if "no_conv_bias" not in controls:
        xbc = xbc + W["conv_bias"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :ds].reshape(t, heads, p)
    b = xbc[:, ds:ds + g * n].reshape(t, g, n)
    c = xbc[:, ds + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + W["dt_bias"])
    a_neg = -jnp.exp(W["A_log"])
    group_of = np.arange(heads) // (heads // g)
    if "wrong_group" in controls:
        group_of = (group_of + 1) % g

    def step(h, xs):  # h (heads, p, n)
        x_t, b_t, c_t, dt_t = xs
        h = (h * jnp.exp(dt_t * a_neg)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t[group_of])

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (x, b, c, dt))
    y = (y + W["D"][:, None] * x).reshape(t, ds) * jax.nn.silu(z)
    y = _rms(y.reshape(t, g, ds // g), W["gate_norm"].reshape(g, -1),
             hf["rms_norm_eps"]).reshape(t, ds)
    return hf["ssm_out_multiplier"] * (y @ _f32(W["ssm_out"]))


def _attention_branch(W, a, hf, controls):
    t = a.shape[0]
    hq, hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    qkv = (hf["attention_in_multiplier"] * a) @ _f32(W["qkv"])
    q = qkv[:, :hq * hd].reshape(t, hq, hd)
    k = qkv[:, hq * hd:(hq + hkv) * hd].reshape(t, hkv, hd)
    if "no_key_multiplier" not in controls:
        k = hf["key_multiplier"] * k
    v = qkv[:, (hq + hkv) * hd:].reshape(t, hkv, hd)
    if "no_rope" not in controls:
        q, k = _rope_half(q, hf["rope_theta"]), _rope_half(k, hf["rope_theta"])
    kv_of = np.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of], v[:, kv_of]
    cols = np.arange(t)[None, :]

    def one_block(args):
        qb, rows = args  # (B, hq, hd), (B,)
        s = jnp.einsum("thd,shd->hts", qb, k) / np.sqrt(hd)
        pr = jax.nn.softmax(
            jnp.where((cols <= rows[:, None])[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v)

    size = min(t, QUERY_BLOCK)
    o = jax.lax.map(one_block, (q.reshape(t // size, size, hq, hd),
                                jnp.arange(t).reshape(t // size, size)))
    return hf["attention_out_multiplier"] * (
        o.reshape(t, hq * hd) @ _f32(W["o"]))


def _mlp_branch(W, f, hf):
    gate_m, down_m = hf["mlp_multipliers"]
    width = W["w1"].shape[1]
    size = width // FFN_BLOCKS if width % FFN_BLOCKS == 0 else width
    out = jnp.zeros_like(f)
    for at in range(0, width, size):  # a block of the columns at a time
        w1, w3 = (_f32(W[k][:, at:at + size]) for k in ("w1", "w3"))
        out = out + (jax.nn.silu(gate_m * (f @ w1)) * (f @ w3)) @ _f32(
            W["w2"][at:at + size])
    return down_m * out


@functools.partial(jax.jit, static_argnames=("hf_items", "controls"))
def _layer(stacked, i, x, hf_items, controls):
    """Layer ``i`` of the stacked tensors (``i`` traced: one compile a
    bucket, and the layer's tensors are sliced where they are used)."""
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    W = {name: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         for name, v in stacked.items()}
    with jax.default_matmul_precision("highest"):
        a = _rms(x, W["in_norm"], eps)
        if "drop_ssm" not in controls:
            x = x + _ssm_branch(W, a, hf, controls)
        if "drop_attention" not in controls:
            x = x + _attention_branch(W, a, hf, controls)
        return x + _mlp_branch(W, _rms(x, W["ffn_norm"], eps), hf)


def bucket_for(n: int) -> int:
    return next(b for b in BUCKETS if b >= n)


def forward(hf: dict, params: dict, history, controls=()) -> dict:
    """``history`` item indices, oldest first.  Returns ``h_last`` (hidden,)
    float32 NumPy — ``lm_head_multiplier`` times the final-normed state,
    what the head multiplies —, ``x_last`` (the residual stream it is the
    norm of) and ``added`` (``x_last`` less the scaled embedding it started
    from: what the layers added)."""
    n = len(history)
    tokens = np.zeros(bucket_for(n), np.int32)
    tokens[:n] = history
    items = tuple(sorted(
        (k, tuple(hf[k]) if isinstance(hf[k], (list, tuple)) else hf[k])
        for k in KEYS))
    controls = tuple(sorted(controls))
    x = hf["embedding_multiplier"] * _f32(params["embed"][tokens])
    x0_last = x[n - 1]
    stacked = {name[2:]: v for name, v in params.items()
               if name.startswith("S.")}
    for i in range(hf["num_hidden_layers"]):
        x = _layer(stacked, np.int32(i), x, items, controls)
    x_last = x[n - 1]
    h_last = hf["lm_head_multiplier"] * _rms(
        x_last, params["final_norm"], hf["rms_norm_eps"])
    h_last, x_last, x0_last = jax.device_get((h_last, x_last, x0_last))
    return {"h_last": np.asarray(h_last, np.float32),
            "x_last": np.asarray(x_last, np.float64),
            "added": np.asarray(x_last, np.float64) - np.asarray(
                x0_last, np.float64)}


def references(hf: dict, params: dict, rows: list, controls=()) -> list:
    """:func:`forward` of each row's history."""
    return [forward(hf, params, row["history"], controls) for row in rows]


def compare(rows: list, refs: list) -> dict:
    """``rows``: dicts with ``history`` and the program's ``h_last`` (what
    its head multiplied) and ``x_last`` (the f32 residual stream at the last
    position); ``refs``: this reference's :func:`forward` of each.  The two
    numbers that are judged:

    * ``added_rel_err``: the error of ``x_last`` over the norm of what the
      LAYERS ADDED to the residual stream (``x_last`` less the embedding
      times ``embedding_multiplier`` it started from): relative to
      ``h_last`` itself the layers' contribution is diluted by the
      embedding, and an error inside one branch drowns in the bf16 rounding
      of ``h_last``'s own elements;
    * ``h_last_rel_err``: the relative L2 error of ``h_last``, which ties
      what the head scored to that residual stream (the final norm and
      ``lm_head_multiplier``).

    An error that is not a number counts as 1e9."""
    worst_err = worst_added = 0.0
    worst_tokens = 0

    def rel(got, want, over):
        err = float(np.linalg.norm(got - want) / np.linalg.norm(over))
        return err if np.isfinite(err) else 1e9

    for row, ref in zip(rows, refs):
        want = ref["h_last"].astype(np.float64)
        worst_err = max(worst_err, rel(
            np.asarray(row["h_last"], np.float64), want, want))
        added = rel(np.asarray(row["x_last"], np.float64), ref["x_last"],
                    ref["added"])
        if added > worst_added:
            worst_added, worst_tokens = added, len(row["history"])
    return {"rows": len(rows), "added_rel_err": worst_added,
            "worst_row_tokens": worst_tokens, "h_last_rel_err": worst_err}


def compare_trunk(hf: dict, params: dict, rows: list, controls=()) -> dict:
    """Each row of the program against this reference's own forward pass
    (:func:`compare`)."""
    return compare(rows, references(hf, params, rows, controls))
