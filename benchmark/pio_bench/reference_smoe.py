"""The benchmark's own plain reference of the state-space / attention model
with routed experts behind every layer, and the trunk comparison that
decides ``correct`` for its cells.  Imports nothing from the program under
test.

The layer equations (``configs/granite-4.0-h-small-l10-ep2.json`` gives the
keys and, under ``assumed``, what the published config leaves open).  ``x0 =
embedding_multiplier * E[token]``, ``E`` the tied table; per layer of kind
``layer_types[i]``, on ``x`` (T, hidden):

    a   = RMSNorm(x; w_in, eps)
    mamba:      p = a W_in                              # [z | x | B | C | dt], no bias
                xBC = SiLU(conv4(p[x|B|C]) + b_conv)    # depthwise causal, zeros before the first event
                dt = softplus(p[dt] + dt_bias),  A = -exp(A_log)
                h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t   # per head; ONE group: every head reads the same B_t, C_t
                y_t = h_t C_t + D x_t
                m = RMSNorm(y * SiLU(z); w_g over ALL channels, eps) W_out
    attention:  [q | k | v] = a W_qkv, no bias, NO rotary embedding
                o = causal softmax(attention_multiplier * q k^T) v,  query head h reads key/value head h // (heads / kv heads)
                m = o W_o
    x   = x + residual_multiplier * m
    f   = RMSNorm(x; w_post, eps)
    l   = f W_r;  S = the num_experts_per_tok largest of l;  g = softmax(l[S])
    x   = x + residual_multiplier * (sum_{e in S, e held} g_e SwiGLU_e(f) + SwiGLU_shared(f))

and ``h_last = RMSNorm(x_last; w_final, eps) / logits_scaling``, what the
tied table multiplies.

Everything is float32 with matmuls at ``highest``; one history at a time;
the recurrence token by token (``lax.scan`` over the state: no chunking);
the whole ``(T, T)`` score matrix, a block of queries at a time; one LAYER a
compiled call, the held experts one at a time (``lax.fori_loop``: one
expert's bf16 weights upcast at a time; a layer's held experts are 1.36 GB
in f32) and the state-space projections as they are (0.41 GB), so that the
reference fits beside the resident model.  What departs from "plain":

* **held experts**: the parameters hold the router's experts
  ``[first_expert_held, first_expert_held + n_held)`` of every layer, this
  chip's share of the deployment.  A pick outside the slice adds nothing,
  here as in the program.  Routing and the softmax are over the picks;
* a history is padded at its END to a bucket length (one compile per
  bucket and kind of layer); every mixer here is causal, so the real
  positions are blind to the padding;
* routing is FORCED to the experts the program picked, as
  ``reference_wmoe`` does and for its reason (near-ties among the logits
  flip under bf16): the weights are this reference's own softmax over ITS
  logits at the program's picks; ``violation`` is how far below this
  reference's own 10th-largest logit the program's worst pick lies, on this
  reference's own trajectory; ``flipped`` counts the (token, layer)
  decisions that differ, reported and not judged.

The parameter dict is the program's: ``head`` (the tied table),
``final_norm`` and ``R<j>.<name>``, every layer of run ``j`` (a run of
equal kinds) stacked on a leading axis.

The CONTROLS are this reference computed wrongly on purpose, each a
mechanism the program could get wrong with well-formed answers (the cell's
comparison must tell each from the sound program, ``check_smoe.py``):
``drop_shared``, ``softmax_over_all`` (the weights a softmax over all the
router's logits), ``no_residual_multiplier``, ``no_embedding_multiplier``,
``attention_scale_rsqrt`` (``1 / sqrt(head size)``), ``rope_on_attention``,
``drop_attention``, ``drop_scan`` (the recurrence's part of ``y`` left out:
the skip alone), ``no_conv_bias``, ``gated_norm_two_groups``,
``unheld_as_held`` (a pick of an expert held elsewhere computed with the
held expert of the same local index).
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (128, 512, 2048, 8192)
# queries of one block of the score matrix
QUERY_BLOCK = 128
# the keys of the model's shape `forward` reads from `hf`
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state",
        "num_experts_per_tok", "first_expert_held", "attention_multiplier",
        "embedding_multiplier", "residual_multiplier", "logits_scaling",
        "rms_norm_eps")
EXPERTS = ("e_w1", "e_w3", "e_w2")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _rope_half(x, theta=10000.0):
    t, _, d = x.shape
    inv = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (np.arange(t, dtype=np.float64)[:, None] * inv[None, :]).astype(
        np.float32)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mamba(W, a, hf, controls):
    t = a.shape[0]
    heads, p, g, n = (hf["mamba_n_heads"], hf["mamba_d_head"],
                      hf["mamba_n_groups"], hf["mamba_d_state"])
    ds = heads * p
    proj = a @ _f32(W["ssm_in"])
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

    def step(h, xs):  # h (heads, p, n)
        x_t, b_t, c_t, dt_t = xs
        h = (h * jnp.exp(dt_t * a_neg)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t[group_of])

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (x, b, c, dt))
    if "drop_scan" in controls:
        y = jnp.zeros_like(y)
    y = (y + W["D"][:, None] * x).reshape(t, ds) * jax.nn.silu(z)
    ng = 2 if "gated_norm_two_groups" in controls else g
    y = _rms(y.reshape(t, ng, ds // ng), W["gate_norm"].reshape(ng, -1),
             hf["rms_norm_eps"]).reshape(t, ds)
    return y @ _f32(W["ssm_out"])


def _attention(W, a, hf, controls):
    t = a.shape[0]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["hidden_size"] // hq
    qkv = a @ _f32(W["qkv"])
    q = qkv[:, :hq * hd].reshape(t, hq, hd)
    k = qkv[:, hq * hd:(hq + hkv) * hd].reshape(t, hkv, hd)
    v = qkv[:, (hq + hkv) * hd:].reshape(t, hkv, hd)
    if "rope_on_attention" in controls:
        q, k = _rope_half(q), _rope_half(k)
    scale = (1.0 / np.sqrt(hd) if "attention_scale_rsqrt" in controls
             else hf["attention_multiplier"])
    kv_of = np.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of], v[:, kv_of]
    cols = np.arange(t)[None, :]

    def one_block(args):
        qb, rows = args  # (B, hq, hd), (B,)
        s = scale * jnp.einsum("thd,shd->hts", qb, k)
        pr = jax.nn.softmax(
            jnp.where((cols <= rows[:, None])[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", pr, v)

    size = min(t, QUERY_BLOCK)
    o = jax.lax.map(one_block, (q.reshape(t // size, size, hq, hd),
                                jnp.arange(t).reshape(t // size, size)))
    return o.reshape(t, hq * hd) @ _f32(W["o"])


@functools.partial(jax.jit, static_argnames=("kind", "hf_items", "controls"))
def _layer(stacked, i, x, picked, n_real, kind, hf_items, controls):
    """Layer ``i`` of a run's stacked tensors (``i`` traced: one compile a
    bucket and kind, and the layer's tensors are sliced where they are
    used).  Returns the stream, the worst routing violation over the real
    tokens, the decisions that differ from this reference's own and whether
    the last position's does."""
    hf = dict(hf_items)
    eps, k, rm = (hf["rms_norm_eps"], hf["num_experts_per_tok"],
                  hf["residual_multiplier"])
    if "no_residual_multiplier" in controls:
        rm = 1.0
    first = hf["first_expert_held"]
    n_held = stacked["e_w1"].shape[1]
    W = {name: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
         for name, v in stacked.items() if name not in EXPERTS}
    real = jnp.arange(x.shape[0]) < n_real
    with jax.default_matmul_precision("highest"):
        a = _rms(x, W["in_norm"], eps)
        if kind == "mamba":
            x = x + rm * _mamba(W, a, hf, controls)
        elif "drop_attention" not in controls:
            x = x + rm * _attention(W, a, hf, controls)
        f = _rms(x, W["ffn_norm"], eps)
        logits = f @ W["gate"]
        own_vals, own = jax.lax.top_k(logits, k)
        at_picks = jnp.take_along_axis(logits, picked, 1)
        viol = jnp.where(real, jnp.maximum(
            own_vals[:, k - 1] - at_picks.min(axis=1), 0.0), 0.0)
        differs = (jnp.sort(own, 1) != jnp.sort(picked, 1)).any(1) & real
        if "softmax_over_all" in controls:
            w = jnp.take_along_axis(jax.nn.softmax(logits, axis=1), picked, 1)
        else:
            w = jax.nn.softmax(at_picks, axis=1)
        local = picked - first
        if "unheld_as_held" in controls:
            local = local % n_held
        out = jnp.zeros_like(f)
        if "drop_shared" not in controls:
            out = _swiglu(f, _f32(W["s_w1"]), _f32(W["s_w3"]),
                          _f32(W["s_w2"]))

        def one_expert(e, out):
            # masked weight: zero where the token did not pick the e-th
            # HELD expert, the router's expert first + e
            w_e = jnp.sum(jnp.where(local == e, w, 0.0), axis=1)
            take = lambda name: _f32(stacked[name][i, e])
            return out + w_e[:, None] * _swiglu(
                f, take("e_w1"), take("e_w3"), take("e_w2"))

        out = jax.lax.fori_loop(0, n_held, one_expert, out)
        x = x + rm * out
    return (x, viol.max(), differs.sum(),
            differs[n_real - 1].astype(jnp.int32))


def bucket_for(n: int) -> int:
    return next(b for b in BUCKETS if b >= n)


def runs_of(layer_types) -> list:
    """The layers as runs of equal kinds, ``[(kind, layers), ...]``: how
    the program's parameter dict stacks them (``R<j>.``)."""
    return [(kind, len(list(group)))
            for kind, group in itertools.groupby(layer_types)]


def forward(hf: dict, params: dict, history, picks, controls=()) -> dict:
    """``history`` item indices, oldest first; ``picks`` (layers,
    len(history), top_k) the program's choices.  Returns ``h_last``
    (hidden,) float32 NumPy — the final-normed state over
    ``logits_scaling``, what the tied table multiplies —, ``x_last`` (the
    residual stream it is the norm of) and ``added`` (``x_last`` less the
    scaled embedding it started from: what the layers added),
    ``violation``, ``flipped`` and ``flipped_last``."""
    n = len(history)
    t = bucket_for(n)
    tokens = np.zeros(t, np.int32)
    tokens[:n] = history
    padded = np.zeros((picks.shape[0], t, picks.shape[2]), np.int32)
    padded[:, :n] = picks
    items = tuple(sorted((k, hf[k]) for k in KEYS))
    controls = tuple(sorted(controls))
    em = (1.0 if "no_embedding_multiplier" in controls
          else hf["embedding_multiplier"])
    x = em * _f32(params["head"][tokens])
    x0_last = x[n - 1]
    viol, flipped, flipped_last, at = 0.0, 0, 0, 0
    for j, (kind, count) in enumerate(runs_of(hf["layer_types"])):
        pre = f"R{j}."
        stacked = {name[len(pre):]: v for name, v in params.items()
                   if name.startswith(pre)}
        for i in range(count):
            x, v, fl, fl_last = _layer(
                stacked, np.int32(i), x, padded[at], np.int32(n), kind, items,
                controls)
            viol = max(viol, float(v))
            flipped += int(fl)
            flipped_last += int(fl_last)
            at += 1
    x_last = x[n - 1]
    h_last = _rms(x_last, params["final_norm"],
                  hf["rms_norm_eps"]) / hf["logits_scaling"]
    h_last, x_last, x0_last = jax.device_get((h_last, x_last, x0_last))
    return {"h_last": np.asarray(h_last, np.float32),
            "x_last": np.asarray(x_last, np.float64),
            "added": np.asarray(x_last, np.float64) - np.asarray(
                x0_last, np.float64),
            "violation": viol, "flipped": flipped,
            "flipped_last": flipped_last, "decisions": n * picks.shape[0]}


def references(hf: dict, params: dict, rows: list, controls=()) -> list:
    """:func:`forward` of each row's history, routing forced to its picks."""
    return [forward(hf, params, row["history"], row["picks"], controls)
            for row in rows]


def compare(rows: list, refs: list) -> dict:
    """``rows``: dicts with ``history``, ``picks`` and the program's
    ``h_last`` (what its head multiplied) and ``x_last`` (the f32 residual
    stream at the last position); ``refs``: this reference's
    :func:`forward` of each.  The three numbers that are judged:

    * ``added_rel_err``: the error of ``x_last`` over the norm of what the
      LAYERS ADDED to the residual stream (``x_last`` less the embedding
      times ``embedding_multiplier`` it started from): relative to
      ``h_last`` itself the layers' contribution is diluted by the
      embedding, and an error inside one sublayer drowns in the bf16
      rounding of ``h_last``'s own elements;
    * ``h_last_rel_err``: the relative L2 error of ``h_last``, which ties
      what the head scored to that residual stream (the final norm and
      ``logits_scaling``);
    * ``route_violation``: the worst routing violation;

    and the flips that are reported.  An error that is not a number counts
    as 1e9."""
    worst_err = worst_added = worst_viol = 0.0
    worst_tokens = flipped = decisions = rows_flipped = rows_flipped_last = 0

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


def compare_trunk(hf: dict, params: dict, rows: list, controls=()) -> dict:
    """Each row of the program against this reference's own forward pass
    (:func:`compare`)."""
    return compare(rows, references(hf, params, rows, controls))
