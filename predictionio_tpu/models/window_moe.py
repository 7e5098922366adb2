"""A window/global grouped-query-attention, sparse-expert causal transformer
as a next-item recommender: the block design of the public ``afmoe`` models
(here read from the ``Trinity-Large-Preview`` config), with the catalog as
its vocabulary and a user's history as its prompt.

``x0 = E_in[token] * sqrt(hidden)`` (``mup_enabled``).  Per layer, on ``x``
(T, hidden), SANDWICH-normed residual blocks — each sublayer's input and
its output are RMS-normed (learned scale, eps from the config), and the
residual is added after the output norm:

* **attention** (every layer): ``a = RMSNorm(x)``; ``[q | k | v | g] = a
  W_qkvg`` with ``num_attention_heads`` query heads and ``num_key_value_heads``
  key/value heads of ``head_dim``; q and k RMS-normed over each head's
  ``head_dim``; on a ``sliding_attention`` layer half-rotation RoPE on q and
  k (position = index in the user's history) and keys ``t - sliding_window <
  s <= t`` of the same history; on a ``full_attention`` layer NO rotary
  embedding and keys ``s <= t``; query head ``h`` reads key/value head ``h //
  (heads / kv heads)``; ``x + RMSNorm(((softmax(q.k / sqrt(head_dim)) v) *
  sigmoid(g)) W_o)`` (``ops/flash_attention.packed_grouped_attention``).
* **feed-forward**: ``m = RMSNorm(x)``; the first ``num_dense_layers``
  layers a dense SwiGLU; the rest ``SwiGLU_shared(m) + sum_j w_j
  SwiGLU_{e_j}(m)`` with ``num_experts_per_tok`` of ``num_experts`` picked
  by ``sigmoid(m W_r) + bias`` and weighed by the unbiased scores,
  normalised (``route_norm``), times ``route_scale`` (``ops/moe.py``);
  ``x + RMSNorm(f)``.

Final RMSNorm, untied head.

**Held experts.**  A model of this family may hold a contiguous slice of
each expert layer's experts: ``num_experts_held`` of them from
``first_expert_held`` (one rank's share under expert parallelism).  The
router keeps its ``num_experts`` outputs and its picks per token; the layer
computes the shared expert and the held experts' part for its tokens, and
what the experts held elsewhere would add is LEFT OUT (as in the
reference): that partial result goes on to the next layer.  Nothing here
stands in for the other ranks or their exchange.

The layers are unrolled (``L<i>.<name>``), not scanned over stacked
weights as ``gdn_hybrid.trunk`` is: a scan's body slices its layer out of
the stack, and for an expert layer that is a copy of its experts (1.8 GB
at the published widths) a pass, where the unrolled program reads them in
place; equal kinds are at most three layers in a row in this family.
What the unrolled layers run with an equal signature they share all the
same, as ONE traced and lowered function a rung
(``latent_moe.SharedBranches``).

Precision: weights and matmul operands bf16, accumulation f32; the residual
stream, norms, RoPE, softmax, the gate's sigmoid and the router (weights
and logits) f32.  The compute dtype follows the weights': the tests also
run the same program on f32 weights.

:func:`forward_packed` is the serving program, with the surface of
``models/latent_moe.py`` (whose ``pack`` / ``flatten`` layout and
``score_head`` it shares); the plain f32 reference of the same equations is
``models/window_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.models import latent_moe as _lm
from predictionio_tpu.models.latent_moe import (
    _mm, _swiglu, rms_norm, score_head,
)
from predictionio_tpu.ops import flash_attention as _fa
from predictionio_tpu.ops import moe as _moe
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops import token_tiles as _tiles

# what `PackedSequenceScorer.stats()["family"]` says of this module's models
FAMILY = "window_moe_sequence"
# the host side of a dispatch is the other packed families', shared
pack, flatten = _lm.pack, _lm.flatten
# the tile the position-wise sublayers run in, on the rungs `runs_in_tiles`
DENSE_TILE = _tiles.DENSE_TILE
WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    """The shape of the model, under the keys of the published config."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    layer_types: tuple
    num_dense_layers: int = 1
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    # the slice of every expert layer's experts held here (None: all)
    num_experts_held: Optional[int] = None
    first_expert_held: int = 0
    # the most recent events of a history that are read
    max_len: int = 2048

    UNSUPPORTED = {
        "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "num_expert_groups": 1, "num_limited_groups": 1,
        "rope_scaling": None, "hidden_act": "silu",
        "tie_word_embeddings": False, "attention_bias": False,
    }

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "WindowMoEConfig":
        """From a published ``config.json``'s keys.  A key that selects a
        mechanism this module does not implement is refused, not ignored."""
        for key, only in cls.UNSUPPORTED.items():
            if key in hf and hf[key] != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: this module implements {only!r} only")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(overrides)
        kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        if (len(cfg.layer_types) != cfg.num_hidden_layers
                or set(cfg.layer_types) - {WINDOW, GLOBAL}
                or cfg.num_attention_heads % cfg.num_key_value_heads
                or cfg.head_dim % 2):
            raise ValueError(
                f"layer_types {cfg.layer_types} do not name "
                f"{cfg.num_hidden_layers} layers of the two kinds, or the "
                "key/value heads do not divide the query heads, or the head "
                "size is odd")
        if not (0 <= cfg.first_expert_held
                and cfg.first_expert_held + cfg.n_held <= cfg.num_experts):
            raise ValueError(
                f"experts [{cfg.first_expert_held}, "
                f"{cfg.first_expert_held + cfg.n_held}) are not among the "
                f"router's {cfg.num_experts}")
        return cfg

    @property
    def n_held(self) -> int:
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def n_window_layers(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def qkvg_width(self) -> int:
        return 2 * self.head_dim * (self.num_attention_heads
                                    + self.num_key_value_heads)

    def param_count(self) -> int:
        """Parameters HELD here (the experts held elsewhere are not)."""
        d, f = self.hidden_size, self.moe_intermediate_size
        attn = d * (self.qkvg_width
                    + self.num_attention_heads * self.head_dim)
        dense = 3 * d * self.intermediate_size
        sparse = (3 * d * f * (self.n_held + self.num_shared_experts)
                  + d * self.num_experts)
        return (2 * self.vocab_size * d + self.num_hidden_layers * attn
                + self.num_dense_layers * dense + self.n_moe_layers * sparse)


def padded_vocab(cfg: WindowMoEConfig) -> int:
    """Head rows as the score kernel sweeps them (whole item blocks)."""
    return _score_kernel.pad_block_items(cfg.vocab_size)


def param_shapes(cfg: WindowMoEConfig) -> dict:
    """``{name: (shape, dtype)}`` of every tensor, layers as ``L<i>.<name>``;
    the flat dict IS the parameter pytree.  ``qkvg`` is ``[W_q | W_k | W_v |
    W_g]`` side by side: the same parameters and products, one matmul."""
    d, hd = cfg.hidden_size, cfg.head_dim
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((cfg.vocab_size, d), bf),
        "head": ((padded_vocab(cfg), d), bf),
        "final_norm": ((d,), f32),
    }
    for i in range(cfg.num_hidden_layers):
        p = f"L{i}."
        out.update({
            p + "in_norm": ((d,), f32),
            p + "qkvg": ((d, cfg.qkvg_width), bf),
            p + "q_norm": ((hd,), f32), p + "k_norm": ((hd,), f32),
            p + "o": ((cfg.num_attention_heads * hd, d), bf),
            p + "post_attn_norm": ((d,), f32),
            p + "pre_mlp_norm": ((d,), f32),
            p + "post_mlp_norm": ((d,), f32),
        })
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            out.update({p + "w1": ((d, f), bf), p + "w3": ((d, f), bf),
                        p + "w2": ((f, d), bf)})
        else:
            f, e, held = (cfg.moe_intermediate_size, cfg.num_experts,
                          cfg.n_held)
            fs = f * cfg.num_shared_experts
            out.update({
                p + "gate": ((d, e), f32), p + "gate_bias": ((e,), f32),
                p + "e_w1": ((held, d, f), bf), p + "e_w3": ((held, d, f), bf),
                p + "e_w2": ((held, f, d), bf),
                p + "s_w1": ((d, fs), bf), p + "s_w3": ((d, fs), bf),
                p + "s_w2": ((fs, d), bf),
            })
    return out


def init_params(cfg: WindowMoEConfig, seed: int, *, std: float = 0.02,
                bias_std: float = 0.01, embed_std: float = 1.0) -> dict:
    """Seeded weights made ON the device, tensor by tensor, as
    ``latent_moe.init_params`` makes them and for its reasons: ``N(0, std)``
    matrices, unit norm scales, ``N(0, bias_std)`` selection biases, unit
    embedding rows, zero rows in the head's padding."""
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


def rope_half(x, positions, theta):
    """Rotary embedding over HALVES of the last axis (``rotate_half``):
    ``[x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin]``, angle ``pos *
    theta**(-2i/d)``; ``x`` (T, heads, d), f32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def runs_in_tiles(t_pad: int, tile: int = DENSE_TILE) -> bool:
    """Whether the program of a rung of ``t_pad`` tokens runs its
    position-wise parts in tiles: it holds MORE than two, whole.  A dispatch
    goes to the smallest rung that holds it, so on a doubling ladder it
    fills more than half of its rung: a rung of two tiles always needs
    both, and tiles would cost it their loops (read on the chip: +1 to
    +7 % at 1,024 tokens) and save nothing.  A shorter rung's program is
    composed sublayer by sublayer, as it was before there were tiles."""
    return t_pad > 2 * tile and t_pad % tile == 0


# A layer is position-wise parts around two cross-token operations, the
# attention kernel and the routed experts' products.  Each part is written
# once, below, on the tensors of ONE layer that it reads (``W``:
# ``layer_weights``, the names without their ``L<i>.``); `trunk` puts them
# together as two residual branches on a short rung (`_attention`,
# `_feed_forward`: the order, and so the arithmetic, there was before tiles)
# and as three segments over the token tiles that hold a real token on a long
# one (`_layer_in_tiles`, which also keeps all but the gate's columns of the
# projection from crossing the kernel).  That the two agree on every real
# row is `tests/test_window_moe.py`'s to hold, at a tile of 16.  Either way
# what the layers call is shared (`_shared`): the first layer with a
# signature traces and lowers it, kernels included, the next ones call it.

_shared = _lm.SharedBranches()
layer_weights = _lm.layer_weights
# the tensors of a layer that each part reads: the attention branch before
# and behind its kernel, the feed-forward branch of a dense layer and, by
# its three parts, of a sparse one
ATTN_IN = ("in_norm", "qkvg", "q_norm", "k_norm")
ATTN_OUT = ("o", "post_attn_norm")
DENSE_FFN = ("pre_mlp_norm", "w1", "w3", "w2", "post_mlp_norm")
ROUTE = ("pre_mlp_norm", "gate", "gate_bias")
EXPERTS = ("e_w1", "e_w3", "e_w2")
FFN_OUT = ("s_w1", "s_w3", "s_w2", "post_mlp_norm")


def _qkvg(cfg, W, kind, x, positions):
    """Before the kernel: q, k and v heads first, (heads, T, head_dim) in
    the compute dtype, and the projection itself (T, qkvg_width) f32, whose
    last columns the output gate reads."""
    t = x.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    cdt = W["qkvg"].dtype  # the compute dtype is the weights' (bf16)
    a = rms_norm(x, W["in_norm"], cfg.rms_norm_eps)
    qkvg = _mm(a, W["qkvg"])
    q_end, k_end, v_end = hq * hd, (hq + hkv) * hd, (hq + 2 * hkv) * hd
    q = rms_norm(qkvg[:, :q_end].reshape(t, hq, hd), W["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(qkvg[:, q_end:k_end].reshape(t, hkv, hd), W["k_norm"],
                 cfg.rms_norm_eps)
    v = qkvg[:, k_end:v_end].reshape(t, hkv, hd)
    if kind == WINDOW:
        q = rope_half(q, positions, cfg.rope_theta)
        k = rope_half(k, positions, cfg.rope_theta)
    heads_first = lambda z: z.transpose(1, 0, 2).astype(cdt)
    return heads_first(q), heads_first(k), heads_first(v), qkvg


def _attend(cfg, kind, interpret, q, k, v, seg_start):
    return _fa.packed_grouped_attention(
        q, k, v, seg_start,
        window=cfg.sliding_window if kind == WINDOW else None,
        scale=1.0 / math.sqrt(cfg.head_dim), interpret=interpret)


def _attention_out(cfg, W, o, qkvg):
    """Behind the kernel: ``o`` (heads, T, head_dim) under the sigmoid of
    the gate's pre-activation — the last ``heads * head_dim`` columns of
    ``qkvg``, which may be those columns alone — through ``W_o`` and the
    output norm."""
    width = o.shape[0] * o.shape[2]
    o = o.transpose(1, 0, 2).reshape(o.shape[1], width).astype(jnp.float32)
    y = _mm(o * jax.nn.sigmoid(qkvg[:, qkvg.shape[1] - width:]), W["o"])
    return rms_norm(y, W["post_attn_norm"], cfg.rms_norm_eps)


def _route(cfg, W, m):
    """The router's picks and weights for the normed ``m``, and ``m`` in
    the experts' dtype."""
    picked, weights, _ = _moe.route_sigmoid_topk(
        m, W["gate"], W["gate_bias"],
        top_k=cfg.num_experts_per_tok, scale=cfg.route_scale,
        normalize=cfg.route_norm)
    return picked, weights, m.astype(W["e_w1"].dtype)


def _held_products(cfg, interpret, W, mb, picked, weights, valid):
    """The held experts' part for their tokens.  Never in tiles: the held
    experts' weights cross HBM once a dispatch, not once a tile, and it
    sorts padded tokens past the last expert itself."""
    return _moe.expert_products(
        mb, picked, weights, W["e_w1"], W["e_w3"], W["e_w2"], valid,
        first=cfg.first_expert_held, n_experts=cfg.num_experts,
        interpret=interpret)


def _shared_and_held(cfg, W, y, mb, picked):
    """The shared expert added, and per token whether any pick is held."""
    if cfg.num_shared_experts:
        y = y + _swiglu(mb, W["s_w1"], W["s_w3"], W["s_w2"])
    local = picked - cfg.first_expert_held
    return y, ((local >= 0) & (local < cfg.n_held)).any(axis=1)


def _sparse_ffn(cfg, interpret, W, m, valid):
    picked, weights, mb = _route(cfg, W, m)
    y, counts = _held_products(cfg, interpret, W, mb, picked, weights, valid)
    y, held = _shared_and_held(cfg, W, y, mb, picked)
    return y, picked, counts, jnp.sum(valid & ~held, dtype=jnp.int32)


# -- a short rung: a layer is its two residual branches -----------------------


@_shared(0, 1, 2)
def _attention(cfg, kind, interpret, W, x, positions, seg_start):
    q, k, v, qkvg = _qkvg(cfg, W, kind, x, positions)
    return _attention_out(
        cfg, W, _attend(cfg, kind, interpret, q, k, v, seg_start), qkvg)


@_shared(0, 1, 2)
def _feed_forward(cfg, dense, interpret, W, x, valid):
    """The branch's output and, of a sparse layer's routing, its picks, the
    held experts' counts and the valid tokens without a held pick."""
    m = rms_norm(x, W["pre_mlp_norm"], cfg.rms_norm_eps)
    if dense:
        f, routed = _swiglu(m, W["w1"], W["w3"], W["w2"]), ()
    else:
        f, *routed = _sparse_ffn(cfg, interpret, W, m, valid)
    return rms_norm(f, W["post_mlp_norm"], cfg.rms_norm_eps), *routed


# -- a long rung: a layer's position-wise parts run in token tiles, as one
# segment before the attention kernel, one between it and the routed experts'
# products (a dense layer's ends the layer) and one behind those: no
# sublayer's (T, intermediate) array is written whole, and of the projection
# only the gate's columns cross the kernel --------------------------------------


@_shared(0, 1, 2)
def _before(cfg, kind, tile, W, x, positions, n_real):
    def before(x, positions):
        q, k, v, qkvg = _qkvg(cfg, W, kind, x, positions)
        return q, k, v, qkvg[:, -cfg.num_attention_heads * cfg.head_dim:]

    return _tiles.over_real_tiles(before, n_real, x, positions, tile=tile,
                                  out_axes=(1, 1, 1, 0))


@_shared(0, 1, 2)
def _between(cfg, dense, tile, W, o, gate, x, n_real):
    eps = cfg.rms_norm_eps

    def between(o, gate, x):
        x = x + _attention_out(cfg, W, o, gate)
        m = rms_norm(x, W["pre_mlp_norm"], eps)
        if dense:
            f = _swiglu(m, W["w1"], W["w3"], W["w2"])
            return x + rms_norm(f, W["post_mlp_norm"], eps)
        return (x, *_route(cfg, W, m))

    return _tiles.over_real_tiles(between, n_real, o, gate, x, tile=tile,
                                  in_axes=(1, 0, 0))


@_shared(0, 1)
def _behind(cfg, tile, W, y, mb, picked, x, n_real):
    def behind(y, mb, picked, x):
        f, held = _shared_and_held(cfg, W, y, mb, picked)
        return x + rms_norm(f, W["post_mlp_norm"], cfg.rms_norm_eps), held

    return _tiles.over_real_tiles(behind, n_real, y, mb, picked, x, tile=tile)


# the two cross-token operations between the segments: a short rung's
# branches hold them inside
_attend_in_tiles = _shared(0, 1, 2)(_attend)
_products_in_tiles = _shared(0, 1)(_held_products)


def _layer_in_tiles(cfg, kind, dense, interpret, tile, P, i, x, positions,
                    seg_start, valid, n_real):
    """Layer ``i`` on the stream in tiles of ``tile`` tokens, the first
    ``n_real`` real: the stream and, of a sparse layer's routing, what
    :func:`_feed_forward` returns (else nothing)."""
    W = functools.partial(layer_weights, P, i)
    q, k, v, gate = _before(cfg, kind, tile, W(ATTN_IN), x, positions, n_real)
    o = _attend_in_tiles(cfg, kind, interpret, q, k, v, seg_start)
    if dense:
        return _between(cfg, dense, tile, W(ATTN_OUT + DENSE_FFN), o, gate,
                        x, n_real), ()
    # `_route` reads the experts' dtype off their first tensor
    x, picked, weights, mb = _between(
        cfg, dense, tile, W(ATTN_OUT + ROUTE + EXPERTS[:1]), o, gate, x,
        n_real)
    y, counts = _products_in_tiles(cfg, interpret, W(EXPERTS), mb, picked,
                                   weights, valid)
    x, held = _behind(cfg, tile, W(FFN_OUT), y, mb, picked, x, n_real)
    return x, (picked, counts, jnp.sum(valid & ~held, dtype=jnp.int32))


def trunk(cfg: WindowMoEConfig, P: dict, tokens, positions, seg_start,
          valid, *, interpret: Optional[bool] = None,
          dense_tile: int = DENSE_TILE):
    """The block stack over a packed token axis.  Returns the residual
    stream (T, hidden) f32 BEFORE the final norm, the picks of every sparse
    layer (L_moe, T, top_k), the valid assignments per HELD expert (L_moe,
    n_held) and, per sparse layer, the valid tokens none of whose picks is
    held (L_moe,).

    On a rung that :func:`runs_in_tiles` of ``dense_tile`` tokens (a
    test's: the program's is the default), what is position-wise (every
    product, norm and gate but the routed experts') runs only the tiles
    that hold a real token (:func:`_layer_in_tiles`): the padded tokens'
    rows of the stream past the last such tile are zeros, no model's
    output."""
    in_tiles = runs_in_tiles(tokens.shape[0], dense_tile)
    if in_tiles:
        # `pack` lays rows end to end from token 0: the real tokens lead
        n_real = jnp.sum(valid, dtype=jnp.int32)
    x = P["embed"][tokens].astype(jnp.float32)
    if cfg.mup_enabled:
        x = x * math.sqrt(cfg.hidden_size)
    picks, counts, unheld = [], [], []
    for i, kind in enumerate(cfg.layer_types):
        dense = i < cfg.num_dense_layers
        if in_tiles:
            x, routed = _layer_in_tiles(
                cfg, kind, dense, interpret, dense_tile, P, i, x, positions,
                seg_start, valid, n_real)
        else:
            x = x + _attention(
                cfg, kind, interpret, layer_weights(P, i, ATTN_IN + ATTN_OUT),
                x, positions, seg_start)
            f, *routed = _feed_forward(
                cfg, dense, interpret, layer_weights(
                    P, i, DENSE_FFN if dense else ROUTE + EXPERTS + FFN_OUT),
                x, valid)
            x = x + f
        if routed:
            picks.append(routed[0])
            counts.append(routed[1])
            unheld.append(routed[2])
    k = cfg.num_experts_per_tok
    return (x,
            jnp.stack(picks) if picks
            else jnp.zeros((0, x.shape[0], k), jnp.int32),
            jnp.stack(counts) if counts
            else jnp.zeros((0, cfg.n_held), jnp.int32),
            jnp.stack(unheld) if unheld else jnp.zeros((0,), jnp.int32))


def attention_counts(cfg: WindowMoEConfig, positions, seg_start, valid):
    """What ONE window layer and ONE global layer of this dispatch are
    asked and what the window layer's sweep runs, (4,) int32: the (query,
    key) pairs the window mask shows (``min(position + 1, window)`` a real
    token), the pairs the causal mask shows, the key blocks the window
    layer's sweep runs and the key blocks a sweep to each history's start
    would run — both over the query blocks that hold a real token."""
    t = positions.shape[0]
    block = min(_fa.PACKED_BLOCK, t)
    reach = positions + 1
    lo, to_start = _fa.sweep_blocks(seg_start, block, cfg.sliding_window)
    qi = jnp.arange(t // block, dtype=jnp.int32)
    real = valid[::block]
    return jnp.stack([
        jnp.sum(jnp.where(valid, jnp.minimum(reach, cfg.sliding_window), 0)),
        jnp.sum(jnp.where(valid, reach, 0)),
        jnp.sum(jnp.where(real, qi - lo + 1, 0)),
        jnp.sum(jnp.where(real, qi - to_start + 1, 0)),
    ]).astype(jnp.int32)


def forward_packed(cfg: WindowMoEConfig, P: dict, tokens, positions,
                   seg_start, valid, last_idx, k: int, *,
                   interpret: Optional[bool] = None,
                   score_backend: Optional[str] = None,
                   dense_tile: int = DENSE_TILE) -> dict:
    """One dispatch: the packed token axis through the trunk, each row's
    last position through the final norm, and its top-``k`` items taken on
    the device.  Arguments as ``latent_moe.forward_packed``; ``dense_tile``
    as :func:`trunk`'s (a test's: the program's is the default).  Returns
    ``values`` and ``indices`` (R, k), ``h_last`` (R, hidden) bf16 and
    ``x_last`` (R, hidden) f32 — the residual stream h_last is the norm of,
    for audits: bf16 hides what five layers add to an embedding 55 times
    their size — ``picks``, ``expert_counts`` (over the HELD experts),
    ``tokens_unheld``, ``attn_counts`` and, on the fused score backend, the
    merge counters."""
    x, picks, counts, unheld = trunk(
        cfg, P, tokens, positions, seg_start, valid, interpret=interpret,
        dense_tile=dense_tile)
    x_last = x[last_idx]
    res = score_head(P, cfg.vocab_size, cfg.rms_norm_eps, x_last, k,
                     interpret=interpret, score_backend=score_backend)
    res.update(x_last=x_last, picks=picks, expert_counts=counts,
               tokens_unheld=unheld,
               attn_counts=attention_counts(cfg, positions, seg_start, valid))
    return res


def forward_flat(cfg: WindowMoEConfig, P: dict, flat, t_pad: int, k: int,
                 **kw) -> dict:
    """:func:`forward_packed` on ``latent_moe.flatten``'s layout."""
    tokens, positions, seg_start, valid = (
        flat[i * t_pad:(i + 1) * t_pad] for i in range(4))
    return forward_packed(cfg, P, tokens, positions, seg_start, valid != 0,
                          flat[4 * t_pad:], k, **kw)


class DispatchCounters:
    """This family's own counters in the packed scorer (``serving/seqpath``
    holds the lock).  Over the sparse layers of every dispatch, under the
    names ``latent_moe``'s have and OVER THE HELD EXPERTS: the experts that
    received a token (their weights crossed HBM), the assignments to them,
    and the busiest one's load over their mean load; beside them all the
    assignments the router made (``tokens x top_k``), the tokens that
    picked no held expert, and the dispatches of a layer whose local
    assignments took more than one pass of ``ops/moe.local_row_bound``
    rows.  Over the attention layers: the (query, key) pairs the window and
    the causal mask show, and the key blocks the window layers' sweep ran
    beside those a sweep to each history's start would run.  Over the
    position-wise sublayers: the token tiles they ran (``dense_tiles``, the
    program's trip count) beside those their rungs hold; a rung whose
    program runs whole counts as one tile, run."""

    # outputs of the program fetched with every dispatch's answer
    fetch = ("expert_counts", "tokens_unheld", "attn_counts")

    def __init__(self, config: WindowMoEConfig):
        self.config = config
        self.experts_touched = 0
        self.expert_assignments = 0
        self.load_max_over_mean_sum = 0.0
        self.sparse_layer_dispatches = 0
        self.routed_assignments = 0
        self.tokens_without_held_expert = 0
        self.local_row_overflows = 0
        self.window_pairs = 0
        self.global_pairs = 0
        self.window_kv_blocks = 0
        self.window_kv_blocks_unskipped = 0
        self.dense_tiles = 0
        self.dense_tiles_rung = 0

    def add(self, t_pad: int, n_rows: int, n_tokens: int, got: dict) -> None:
        cfg = self.config
        counts = got["expert_counts"]  # (sparse layers, held experts)
        live = counts.sum(axis=1) > 0
        ratios = counts[live].max(axis=1) / counts[live].mean(axis=1)
        self.experts_touched += int((counts > 0).sum())
        self.expert_assignments += int(counts.sum())
        self.load_max_over_mean_sum += float(ratios.sum())
        self.sparse_layer_dispatches += int(live.sum())
        k = cfg.num_experts_per_tok
        self.routed_assignments += cfg.n_moe_layers * n_tokens * k
        self.tokens_without_held_expert += int(got["tokens_unheld"].sum())
        bound = _moe.local_row_bound(t_pad * k, cfg.n_held, cfg.num_experts)
        self.local_row_overflows += int((counts.sum(axis=1) > bound).sum())
        w_pairs, g_pairs, ran, unskipped = (int(v) for v in got["attn_counts"])
        n_w = cfg.n_window_layers
        self.window_pairs += n_w * w_pairs
        self.global_pairs += (cfg.num_hidden_layers - n_w) * g_pairs
        self.window_kv_blocks += n_w * ran
        self.window_kv_blocks_unskipped += n_w * unskipped
        in_tiles = runs_in_tiles(t_pad)
        self.dense_tiles += (
            _tiles.dense_tiles(t_pad, n_tokens) if in_tiles else 1)
        self.dense_tiles_rung += (
            _tiles.dense_tiles(t_pad, t_pad) if in_tiles else 1)

    def stats(self) -> dict:
        cfg = self.config
        return {
            "sparse_layers": cfg.n_moe_layers,
            "experts": cfg.num_experts,
            "experts_held": cfg.n_held,
            "first_expert_held": cfg.first_expert_held,
            "window": cfg.sliding_window,
            "window_layers": cfg.n_window_layers,
            "global_layers": cfg.num_hidden_layers - cfg.n_window_layers,
            "experts_touched": self.experts_touched,
            "expert_assignments": self.expert_assignments,
            "load_max_over_mean_sum": round(self.load_max_over_mean_sum, 4),
            "sparse_layer_dispatches": self.sparse_layer_dispatches,
            "routed_assignments": self.routed_assignments,
            "tokens_without_held_expert": self.tokens_without_held_expert,
            "local_row_overflows": self.local_row_overflows,
            "window_pairs": self.window_pairs,
            "global_pairs": self.global_pairs,
            "window_kv_blocks": self.window_kv_blocks,
            "window_kv_blocks_unskipped": self.window_kv_blocks_unskipped,
            "dense_tile": DENSE_TILE,
            "dense_tiles": self.dense_tiles,
            "dense_tiles_rung": self.dense_tiles_rung,
            **_shared.stats(),
        }


@dataclasses.dataclass
class WindowMoEModel:
    """What the sequence template serves: the config, the parameter pytree
    (device-resident, or NumPy after a pickle round trip), the item id map,
    and optionally where histories come from (``histories``; None = the
    event store)."""

    config: WindowMoEConfig
    params: dict
    item_map: object
    histories: object = None


Config, Model = WindowMoEConfig, WindowMoEModel
