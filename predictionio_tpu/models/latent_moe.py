"""A latent-attention, sparse-expert causal transformer as a next-item
recommender: the block design of DeepSeek-V3-style models (here read from
the public ``JoyAI-LLM-Flash`` config), with the catalog as its vocabulary
and a user's history as its prompt.

Per layer, on ``x`` (T, hidden), pre-norm residual blocks (RMSNorm, eps from
the config, learned scale):

* **attention** (every layer): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` →
  heads × ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``, heads × ``[k_nope | v] = c_kv W_kvb``; interleaved RoPE on
  ``q_rope`` and on ``k_r`` (ONE rotary key per position, shared by every
  head; position = index in the user's history); ``softmax((q_nope·k_nope +
  q_rope·k_r) / sqrt(d_nope + d_rope))·v``, causal within a history; ``W_o``.
* **feed-forward**: the first ``first_k_dense_replace`` layers a dense
  SwiGLU; the rest ``sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)`` with
  ``top_k`` of ``n_routed_experts`` picked by ``sigmoid(x W_g) + bias``
  (``noaux_tc``, one group) and weighed by the unbiased scores, normalised,
  times ``routed_scaling_factor`` (``ops/moe.py``; every routed token is
  computed, none dropped).

Final RMSNorm, untied head.  The multi-token-prediction module of the
published model is a training-time head and is not part of this module.

Precision: weights and matmul operands bf16, accumulation f32; the residual
stream, norms, softmax and the router (weights and logits) f32.  The
compute dtype follows the weights': the tests also run the same program on
f32 weights, where it must meet the reference to rounding.

The layers are a Python loop over unrolled weights (``L<i>.<name>``), and
a layer is two residual branches.  Branches that present an equal signature
— every layer's attention, every sparse layer's feed-forward — are ONE
function under ``jax.jit`` (:class:`SharedBranches`): a rung's program
traces and lowers each body, kernels included, once, not once a layer.

:func:`forward_packed` is the serving program: several histories packed
into one token axis (``seg_start`` marks them), the last position of each
row scored against the head by ``ops/topk.gather_score_topk`` on the
device.  The plain f32 reference of the same equations is
``models/latent_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import moe as _moe
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops.latent_attention import mla_attention
from predictionio_tpu.ops.topk import gather_score_topk, resolve_backend

# what `PackedSequenceScorer.stats()["family"]` says of this module's models
FAMILY = "latent_moe_sequence"


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """The shape of the model, under the keys of the published config."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    first_k_dense_replace: int = 1
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # the most recent events of a history that are read
    max_len: int = 2048

    UNSUPPORTED = {
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "rope_scaling": None, "attention_bias": False,
        "rope_interleave": True, "hidden_act": "silu", "moe_layer_freq": 1,
        "tie_word_embeddings": False,
    }

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "LatentMoEConfig":
        """From a published ``config.json``'s keys.  A key that selects a
        mechanism this module does not implement is refused, not ignored."""
        for key, only in cls.UNSUPPORTED.items():
            if key in hf and hf[key] != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: this module implements {only!r} only")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(overrides)
        return cls(**kw)

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def param_count(self) -> int:
        d, h = self.hidden_size, self.num_attention_heads
        attn = (d * self.q_lora_rank + self.q_lora_rank * h * self.qk_head_dim
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * h
                * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)
        dense = 3 * d * self.intermediate_size
        f = self.moe_intermediate_size
        sparse = (3 * d * f * (self.n_routed_experts + self.n_shared_experts)
                  + d * self.n_routed_experts)
        return (2 * self.vocab_size * d + self.num_hidden_layers * attn
                + self.first_k_dense_replace * dense
                + self.n_moe_layers * sparse)


def padded_vocab(cfg: LatentMoEConfig) -> int:
    """Head rows as the score kernel sweeps them (whole item blocks)."""
    return _score_kernel.pad_block_items(cfg.vocab_size)


def param_shapes(cfg: LatentMoEConfig) -> dict:
    """``{name: (shape, dtype)}`` of every tensor, layers as ``L<i>.<name>``;
    the flat dict IS the parameter pytree."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((cfg.vocab_size, d), bf),
        "head": ((padded_vocab(cfg), d), bf),
        "final_norm": ((d,), f32),
    }
    for i in range(cfg.num_hidden_layers):
        p = f"L{i}."
        out.update({
            p + "attn_norm": ((d,), f32),
            p + "q_a": ((d, cfg.q_lora_rank), bf),
            p + "q_a_norm": ((cfg.q_lora_rank,), f32),
            p + "q_b": ((cfg.q_lora_rank, h * cfg.qk_head_dim), bf),
            p + "kv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), bf),
            p + "kv_a_norm": ((cfg.kv_lora_rank,), f32),
            p + "kv_b": ((cfg.kv_lora_rank,
                          h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), bf),
            p + "o": ((h * cfg.v_head_dim, d), bf),
            p + "ffn_norm": ((d,), f32),
        })
        if i < cfg.first_k_dense_replace:
            f = cfg.intermediate_size
            out.update({p + "w1": ((d, f), bf), p + "w3": ((d, f), bf),
                        p + "w2": ((f, d), bf)})
        else:
            f, e = cfg.moe_intermediate_size, cfg.n_routed_experts
            fs = f * cfg.n_shared_experts
            out.update({
                p + "gate": ((d, e), f32), p + "gate_bias": ((e,), f32),
                p + "e_w1": ((e, d, f), bf), p + "e_w3": ((e, d, f), bf),
                p + "e_w2": ((e, f, d), bf),
                p + "s_w1": ((d, fs), bf), p + "s_w3": ((d, fs), bf),
                p + "s_w2": ((fs, d), bf),
            })
    return out


def init_params(cfg: LatentMoEConfig, seed: int, *, std: float = 0.02,
                bias_std: float = 0.01, embed_std: float = 1.0) -> dict:
    """Seeded weights made ON the device, tensor by tensor: ``N(0, std)``
    matrices, unit norm scales, ``N(0, bias_std)`` selection biases (so
    that selecting and weighing differ), zero rows in the head's padding.

    Embedding rows are ``N(0, embed_std)`` with ``embed_std`` 1, not
    ``std``: an untrained attention layer averages its values almost
    uniformly, so every later position of a history receives nearly the
    same vector, and against rows of norm 0.9 that vector IS the residual
    stream: every token then routes to the same few experts (measured on
    the chip at ``embed_std`` 0.02: the busiest expert 12 x the mean load).
    A trained model's tokens differ; unit-scale rows keep them apart.

    The ``rbg`` generator: 5.5 G normals take seconds where the default
    threefry takes 40 s on a v5e."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) % (2 ** 32), impl="rbg"), int(seed) >> 32)

    @functools.partial(jax.jit, static_argnames=("shape", "dtype"))
    def draw(k, s, shape, dtype):  # f32 draws, cast inside the one program
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    params = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(
            param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, dtype)
        elif name == "head":
            real = draw(k, std, (cfg.vocab_size, shape[1]), dtype)
            params[name] = jnp.pad(
                real, ((0, shape[0] - cfg.vocab_size), (0, 0)))
        else:
            s = (bias_std if name.endswith("gate_bias")
                 else embed_std if name == "embed" else std)
            params[name] = draw(k, s, shape, dtype)
    return params


# -- the blocks ---------------------------------------------------------------


class SharedBranches:
    """The residual branches that the layers of a family's programs share.

    ``@shared(0, 1)`` on ``body(<static arguments>, W, *arrays)`` makes the
    branch ONE function under ``jax.jit`` (the numbered arguments static):
    the first layer of a program that hands it a signature (the static
    arguments, the shapes of the layer's own weights ``W`` — handed in
    without their ``L<i>.`` prefix, so that two layers' are equal — and of
    the arrays) traces and lowers the body, kernels included; a later layer
    with an equal one gets the cached jaxpr back and lowers to a call of the
    one private function.  XLA inlines those calls before it fuses, so the
    executable is that of the bodies composed layer by layer.
    ``pallas_call`` keeps no trace cache of its own: composed in a Python
    loop, every kernel of every layer is traced and lowered again, and
    those are most of a program's set-up once its executable is cached.

    ``traces`` counts the times Python ran a body (it does only while JAX
    traces it), ``calls`` the times a layer called one, in this process."""

    def __init__(self):
        self.traces = self.calls = 0

    def __call__(self, *static_argnums):
        def share(body):
            @functools.partial(jax.jit, static_argnums=static_argnums)
            @functools.wraps(body)
            def traced(*args):
                self.traces += 1
                return body(*args)

            @functools.wraps(body)
            def call(*args):
                self.calls += 1
                return traced(*args)
            return call
        return share

    def stats(self) -> dict:
        return {"branch_traces": self.traces, "branch_calls": self.calls}


def layer_weights(P: dict, i: int, names) -> dict:
    """Layer ``i``'s tensors of ``names``, under those names alone."""
    return {n: P[f"L{i}.{n}"] for n in names}


_shared = SharedBranches()
# the tensors of a layer that each of its branches reads
ATTENTION = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "kv_b", "o")
DENSE_FFN = ("ffn_norm", "w1", "w3", "w2")
SPARSE_FFN = ("ffn_norm", "gate", "gate_bias", "e_w1", "e_w3", "e_w2",
              "s_w1", "s_w3", "s_w2")


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope_interleaved(x, positions, theta):
    """Rotary embedding over INTERLEAVED pairs ``(x[2i], x[2i+1])`` of the
    last axis, angle ``pos * theta**(-2i/d)``; ``x`` (..., T, d), f32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _mm(x, w):
    """bf16 operands, f32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _swiglu(x, w1, w3, w2):
    h = jax.nn.silu(_mm(x, w1)) * _mm(x, w3)
    return _mm(h, w2)


@_shared(0, 1)
def _attention(cfg, interpret, W, x, positions, seg_start):
    t = x.shape[0]
    h, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    bf = W["o"].dtype  # the compute dtype is the weights' (bf16)
    xn = rms_norm(x, W["attn_norm"], cfg.rms_norm_eps)
    c_q = rms_norm(_mm(xn, W["q_a"]), W["q_a_norm"], cfg.rms_norm_eps)
    q = _mm(c_q, W["q_b"]).reshape(t, h, dn + dr).transpose(1, 0, 2)
    kv = _mm(xn, W["kv_a"])
    c_kv = rms_norm(kv[:, :cfg.kv_lora_rank], W["kv_a_norm"],
                    cfg.rms_norm_eps)
    k_r = rope_interleaved(kv[:, cfg.kv_lora_rank:], positions,
                           cfg.rope_theta)
    kvb = _mm(c_kv, W["kv_b"]).reshape(t, h, dn + dv).transpose(1, 0, 2)
    q_r = rope_interleaved(q[..., dn:], positions, cfg.rope_theta)
    o = mla_attention(
        q[..., :dn].astype(bf), q_r.astype(bf), kvb[..., :dn].astype(bf),
        k_r.astype(bf), kvb[..., dn:].astype(bf), seg_start,
        scale=1.0 / math.sqrt(dn + dr), interpret=interpret)
    return _mm(o.transpose(1, 0, 2).reshape(t, h * dv), W["o"])


@_shared(0)
def _dense_ffn(cfg, W, x):
    xn = rms_norm(x, W["ffn_norm"], cfg.rms_norm_eps)
    return _swiglu(xn, W["w1"], W["w3"], W["w2"])


@_shared(0, 1)
def _sparse_ffn(cfg, interpret, W, x, valid):
    xn = rms_norm(x, W["ffn_norm"], cfg.rms_norm_eps)
    picked, weights, _ = _moe.route_sigmoid_topk(
        xn, W["gate"], W["gate_bias"],
        top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
        normalize=cfg.norm_topk_prob)
    xb = xn.astype(W["e_w1"].dtype)
    y, counts = _moe.expert_products(
        xb, picked, weights, W["e_w1"], W["e_w3"], W["e_w2"],
        valid, interpret=interpret)
    shared = _swiglu(xb, W["s_w1"], W["s_w3"], W["s_w2"])
    return y + shared, picked, counts


def trunk(cfg: LatentMoEConfig, P: dict, tokens, positions, seg_start,
          valid=None, *, interpret: Optional[bool] = None):
    """The block stack over a packed token axis.  Returns the residual
    stream (T, hidden) f32 BEFORE the final norm, the picks of every sparse
    layer (L_moe, T, top_k) and the valid assignments per expert
    (L_moe, E)."""
    x = P["embed"][tokens].astype(jnp.float32)
    picks, counts = [], []
    for i in range(cfg.num_hidden_layers):
        x = x + _attention(cfg, interpret, layer_weights(P, i, ATTENTION), x,
                           positions, seg_start)
        if i < cfg.first_k_dense_replace:
            x = x + _dense_ffn(cfg, layer_weights(P, i, DENSE_FFN), x)
        else:
            y, picked, c = _sparse_ffn(
                cfg, interpret, layer_weights(P, i, SPARSE_FFN), x, valid)
            x = x + y
            picks.append(picked)
            counts.append(c)
    k = cfg.num_experts_per_tok
    return (x,
            jnp.stack(picks) if picks
            else jnp.zeros((0, x.shape[0], k), jnp.int32),
            jnp.stack(counts) if counts
            else jnp.zeros((0, cfg.n_routed_experts), jnp.int32))


def forward_packed(cfg: LatentMoEConfig, P: dict, tokens, positions,
                   seg_start, valid, last_idx, k: int, *,
                   interpret: Optional[bool] = None,
                   score_backend: Optional[str] = None) -> dict:
    """One dispatch: the packed token axis through the trunk, each row's
    last position through the final norm, and its top-``k`` items by
    ``h_last @ head^T`` taken on the device.

    ``tokens``/``positions``/``seg_start`` (T,) int32, ``valid`` (T,) bool,
    ``last_idx`` (R,) int32 (a padded row repeats a real row's index: equal
    rows cost the score kernel's merge nothing more).  Returns ``values``
    and ``indices`` (R, k), ``h_last`` (R, hidden) bf16 — what the head
    scored — ``picks``, ``expert_counts`` and, on the fused score backend,
    the merge counters.
    """
    x, picks, counts = trunk(cfg, P, tokens, positions, seg_start, valid,
                             interpret=interpret)
    res = score_head(P, cfg.vocab_size, cfg.rms_norm_eps, x[last_idx], k,
                     interpret=interpret, score_backend=score_backend)
    res.update(picks=picks, expert_counts=counts)
    return res


def score_head(P: dict, vocab_size: int, eps: float, x_last, k: int, *,
               interpret: Optional[bool] = None,
               score_backend: Optional[str] = None) -> dict:
    """What every packed sequence family ends in: each row's last residual
    state ``x_last`` (R, hidden) through the final norm, and its top-``k``
    items by ``h_last @ head^T`` taken on the device.  Returns ``values``
    and ``indices`` (R, k), ``h_last`` (R, hidden) in the head's dtype —
    what the head scored — and, on the fused score backend, ``merge``."""
    h_last = rms_norm(x_last, P["final_norm"], eps).astype(P["head"].dtype)
    # the score kernel's lane row, made in the form it reads
    pad_mask = (
        jnp.arange(P["head"].shape[0], dtype=jnp.int32)[None, :]
        >= vocab_size
    ).astype(jnp.int32)
    be = resolve_backend(score_backend)
    outs = gather_score_topk(
        h_last, P["head"], jnp.arange(x_last.shape[0], dtype=jnp.int32), k,
        item_mask=pad_mask, backend=be, interpret=interpret,
        with_stats=be == "fused")
    res = {"values": outs[0], "indices": outs[1], "h_last": h_last}
    if be == "fused":
        res["merge"] = outs[2]
    return res


def pack(histories, t_pad: int, r_pad: int) -> dict:
    """Host side of one dispatch: item-index histories (oldest first, each
    non-empty) laid end to end on a ``t_pad`` token axis.  A padded token is
    a history of its own at position 0; a padded row repeats row 0."""
    n_tok = sum(len(h) for h in histories)
    if not histories or n_tok > t_pad or len(histories) > r_pad:
        raise ValueError(
            f"{len(histories)} rows / {n_tok} tokens do not fit "
            f"{r_pad} rows / {t_pad} tokens")
    tokens = np.zeros(t_pad, np.int32)
    positions = np.zeros(t_pad, np.int32)
    seg_start = np.arange(t_pad, dtype=np.int32)
    valid = np.zeros(t_pad, np.bool_)
    last_idx = np.zeros(r_pad, np.int32)
    at = 0
    for r, h in enumerate(histories):
        n = len(h)
        tokens[at:at + n] = h
        positions[at:at + n] = np.arange(n)
        seg_start[at:at + n] = at
        at += n
        last_idx[r] = at - 1
    valid[:at] = True
    last_idx[len(histories):] = last_idx[0]
    return {"tokens": tokens, "positions": positions, "seg_start": seg_start,
            "valid": valid, "last_idx": last_idx}


def flatten(batch: dict) -> np.ndarray:
    """One int32 array per dispatch (one host-to-device copy, not five):
    ``[tokens | positions | seg_start | valid | last_idx]``."""
    return np.concatenate([
        batch["tokens"], batch["positions"], batch["seg_start"],
        batch["valid"].astype(np.int32), batch["last_idx"]])


def forward_flat(cfg: LatentMoEConfig, P: dict, flat, t_pad: int, k: int,
                 **kw) -> dict:
    """:func:`forward_packed` on :func:`flatten`'s layout."""
    tokens, positions, seg_start, valid = (
        flat[i * t_pad:(i + 1) * t_pad] for i in range(4))
    return forward_packed(cfg, P, tokens, positions, seg_start, valid != 0,
                          flat[4 * t_pad:], k, **kw)


class DispatchCounters:
    """This family's own counters in the packed scorer (``serving/seqpath``
    holds the lock): over the sparse layers of every dispatch, the experts
    that received a token (their weights crossed HBM), the assignments, and
    the busiest expert's load over the mean load."""

    # outputs of the program fetched with every dispatch's answer
    fetch = ("expert_counts",)

    def __init__(self, config: LatentMoEConfig):
        self.config = config
        self.experts_touched = 0
        self.expert_assignments = 0
        self.load_max_over_mean_sum = 0.0
        self.sparse_layer_dispatches = 0

    def add(self, t_pad: int, n_rows: int, n_tokens: int, got: dict) -> None:
        counts = got["expert_counts"]  # (sparse layers, experts)
        live = counts.sum(axis=1) > 0
        ratios = counts[live].max(axis=1) / counts[live].mean(axis=1)
        self.experts_touched += int((counts > 0).sum())
        self.expert_assignments += int(counts.sum())
        self.load_max_over_mean_sum += float(ratios.sum())
        self.sparse_layer_dispatches += int(live.sum())

    def stats(self) -> dict:
        return {
            "sparse_layers": self.config.n_moe_layers,
            "experts": self.config.n_routed_experts,
            "experts_touched": self.experts_touched,
            "expert_assignments": self.expert_assignments,
            "load_max_over_mean_sum": round(self.load_max_over_mean_sum, 4),
            "sparse_layer_dispatches": self.sparse_layer_dispatches,
            **_shared.stats(),
        }


@dataclasses.dataclass
class LatentMoEModel:
    """What the sequence template serves: the config, the parameter pytree
    (device-resident, or NumPy after a pickle round trip), the item id map,
    and optionally where histories come from (``histories``; None = the
    event store)."""

    config: LatentMoEConfig
    params: dict
    item_map: object
    histories: object = None


# the names the sequence template and the packed scorer find a family's
# parts under (models/gdn_hybrid.py has the same)
Config, Model = LatentMoEConfig, LatentMoEModel
