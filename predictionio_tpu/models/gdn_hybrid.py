"""A hybrid of gated-delta-rule linear-attention layers and full-attention
layers as a next-item recommender: the block design of ``olmo_hybrid``
models (here read from the public ``Olmo-Hybrid-7B`` config), with the
catalog as its vocabulary and a user's history as its prompt.

``layer_types`` gives the pattern (three ``linear_attention`` layers to
every ``full_attention`` layer); every layer is two post-normed residual
sublayers, ``x + RMSNorm(mixer(x))`` then ``x + RMSNorm(SwiGLU(x))``
(RMSNorm with a learned scale, eps from the config).  On ``x`` (T, hidden):

* **linear layer** (the ``GatedDeltaNet`` mixer): ``[q~ | k~ | v~] = x
  W_qkv``; each channel through a causal depthwise convolution of width
  ``linear_conv_kernel_dim`` over the history (zeros before its first
  event, no bias) and SiLU; heads x ``q, k`` in R^dk, ``v`` in R^dv; ``q <-
  q/|q| dk^-1/2``, ``k <- k/|k|``; per head ``beta = sigmoid(x W_b)`` (x 2
  with ``linear_allow_neg_eigval``) and ``g = -exp(A_log) softplus(x W_a +
  dt_bias)``; the recurrence ``S_t = e^g S_(t-1) + beta k (v - e^g
  S_(t-1)^T k)^T``, ``o = S_t^T q`` from ``S = 0`` at the history's first
  event (``ops/gated_delta.py``, chunked, over the packed axis); output
  ``[RMSNorm_dv(o) * SiLU(x W_g)] W_o``.
* **full layer**: ``[q | k | v] = x W_qkv``, q and k RMS-normed over the
  whole projection, heads x ``hidden / heads``; causal softmax attention
  within a history with NO rotary embedding (``rope_theta`` is null in the
  published config; the recurrent layers carry order); ``W_o``
  (``ops/flash_attention.packed_causal_attention``).

Final RMSNorm, untied head.  The layers of one PERIOD of the pattern are
compiled once and scanned over the periods (``lax.scan`` over weights
stacked on a leading axis, ``S<slot>.<name>``): sixteen layers compile in
four layers' time.

Precision: weights and matmul operands bf16, accumulation f32; the residual
stream, norms, softmax, the convolution, the gates and the scan's state
f32.  The compute dtype follows the weights': the tests also run the same
program on f32 weights.

:func:`forward_packed` is the serving program, with the surface of
``models/latent_moe.py`` (whose ``pack`` / ``flatten`` layout and
``score_head`` it shares); the plain f32 reference of the same equations is
``models/gdn_hybrid_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models import latent_moe as _lm
from predictionio_tpu.models.latent_moe import (
    _mm, _swiglu, rms_norm, score_head,
)
from predictionio_tpu.ops import gated_delta as _gd
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops.flash_attention import packed_causal_attention

# what `PackedSequenceScorer.stats()["family"]` says of this module's models
FAMILY = "gdn_hybrid_sequence"
# the host side of a dispatch is the other packed family's, shared
pack, flatten = _lm.pack, _lm.flatten
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class GDNHybridConfig:
    """The shape of the model, under the keys of the published config."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    intermediate_size: int
    num_attention_heads: int
    layer_types: tuple
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    # the most recent events of a history that are read
    max_len: int = 2048

    UNSUPPORTED = {
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False,
        "rope_parameters": {"rope_theta": None},
    }

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "GDNHybridConfig":
        """From a published ``config.json``'s keys.  A key that selects a
        mechanism this module does not implement is refused, not ignored."""
        for key, only in cls.UNSUPPORTED.items():
            if key in hf and hf[key] != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: this module implements {only!r} only")
        heads = hf["num_attention_heads"]
        for key, only in (("num_key_value_heads", heads),
                          ("linear_num_key_heads",
                           hf["linear_num_value_heads"])):
            if hf.get(key, only) != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: grouped heads are not implemented "
                    f"(this model has {only})")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(overrides)
        kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        if (len(cfg.layer_types) != cfg.num_hidden_layers
                or set(cfg.layer_types) - {LINEAR, FULL}
                or cfg.hidden_size % cfg.num_attention_heads):
            raise ValueError(
                f"layer_types {cfg.layer_types} do not name "
                f"{cfg.num_hidden_layers} layers of the two kinds, or the "
                "heads do not divide the hidden size")
        return cfg

    @property
    def period(self) -> tuple:
        """The shortest pattern ``layer_types`` repeats."""
        kinds = self.layer_types
        for n in range(1, len(kinds) + 1):
            if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
                return kinds[:n]
        return kinds

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // len(self.period)

    @property
    def n_linear_layers(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def qkv_width(self) -> int:
        return self.linear_num_value_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim)

    def param_count(self) -> int:
        d, h = self.hidden_size, self.linear_num_value_heads
        dv = h * self.linear_value_head_dim
        linear = d * self.qkv_width + d * 2 * h + 2 * d * dv
        full = 4 * d * d
        ffn = 3 * d * self.intermediate_size
        n_lin = self.n_linear_layers
        return (2 * self.vocab_size * d + n_lin * linear
                + (self.num_hidden_layers - n_lin) * full
                + self.num_hidden_layers * ffn)


def padded_vocab(cfg: GDNHybridConfig) -> int:
    """Head rows as the score kernel sweeps them (whole item blocks)."""
    return _score_kernel.pad_block_items(cfg.vocab_size)


def param_shapes(cfg: GDNHybridConfig) -> dict:
    """``{name: (shape, dtype)}`` of every tensor; the flat dict IS the
    parameter pytree.  ``S<j>.<name>`` holds slot ``j`` of every period,
    stacked on the leading axis."""
    d, f, n = cfg.hidden_size, cfg.intermediate_size, cfg.n_periods
    h, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": ((cfg.vocab_size, d), bf),
        "head": ((padded_vocab(cfg), d), bf),
        "final_norm": ((d,), f32),
    }
    for j, kind in enumerate(cfg.period):
        p = f"S{j}."
        if kind == LINEAR:
            out.update({
                p + "qkv": ((n, d, cfg.qkv_width), bf),
                p + "conv": ((n, cfg.linear_conv_kernel_dim, cfg.qkv_width),
                             bf),
                p + "ab": ((n, d, 2 * h), bf),
                p + "A_log": ((n, h), f32), p + "dt_bias": ((n, h), f32),
                p + "gate": ((n, d, h * dv), bf),
                p + "o_norm": ((n, dv), f32),
                p + "o": ((n, h * dv, d), bf),
            })
        else:
            out.update({
                p + "qkv": ((n, d, 3 * d), bf),
                p + "q_norm": ((n, d), f32), p + "k_norm": ((n, d), f32),
                p + "o": ((n, d, d), bf),
            })
        out.update({
            p + "attn_norm": ((n, d), f32), p + "ffn_norm": ((n, d), f32),
            p + "w1": ((n, d, f), bf), p + "w3": ((n, d, f), bf),
            p + "w2": ((n, f, d), bf),
        })
    return out


def init_params(cfg: GDNHybridConfig, seed: int, *, std: float = 0.02,
                embed_std: float = 1.0) -> dict:
    """Seeded weights made ON the device, tensor by tensor: ``N(0, std)``
    matrices, unit norm scales, unit-scale embedding rows (as
    ``latent_moe.init_params``: untrained mixers average, and distinct
    tokens must stay distinct), zero rows in the head's padding, and the
    public ``GatedDeltaNet`` layer's own initial values where it has them:
    convolution weights ``U(-1/2, 1/2)`` (a width-4 depthwise Conv1d's
    default), ``A = exp(A_log) ~ U(0, 16)`` and ``dt_bias`` the inverse
    softplus of ``dt`` log-uniform in [1e-3, 1e-1], so that the decay
    ``exp(g)`` spans about 0.2-0.999 across heads and tokens."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) % (2 ** 32), impl="rbg"), int(seed) >> 32)

    @functools.partial(jax.jit, static_argnames=("shape", "dtype"))
    def normal(k, s, shape, dtype):  # f32 draws, cast inside the one program
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("shape",))
    def uniform(k, lo, hi, shape):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    params = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(
            param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, dtype)
        elif name == "head":
            real = normal(k, std, (cfg.vocab_size, shape[1]), dtype)
            params[name] = jnp.pad(
                real, ((0, shape[0] - cfg.vocab_size), (0, 0)))
        elif name.endswith(".conv"):
            params[name] = uniform(k, -0.5, 0.5, shape).astype(dtype)
        elif name.endswith(".A_log"):
            params[name] = jnp.log(uniform(k, 1e-3, 16.0, shape))
        elif name.endswith(".dt_bias"):
            dt = jnp.exp(uniform(k, np.log(1e-3), np.log(1e-1), shape))
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            params[name] = normal(
                k, embed_std if name == "embed" else std, shape, dtype)
    return params


# -- the blocks ---------------------------------------------------------------


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _heads_first(x, heads, dtype):
    """(T, heads * d) -> (heads, T, d), the kernels' layout."""
    t = x.shape[0]
    return x.reshape(t, heads, -1).transpose(1, 0, 2).astype(dtype)


def _linear_mixer(cfg, W, x, positions, seg_start, n_real, interpret):
    t = x.shape[0]
    h, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    cdt = W["o"].dtype  # the compute dtype is the weights' (bf16)
    qkv = jax.nn.silu(_gd.causal_conv(_mm(x, W["qkv"]), W["conv"], positions))
    q = _l2norm(qkv[:, :h * dk].reshape(t, h, dk)) * dk ** -0.5
    k = _l2norm(qkv[:, h * dk:2 * h * dk].reshape(t, h, dk))
    ab = _mm(x, W["ab"])
    beta = jax.nn.sigmoid(ab[:, h:])
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(W["A_log"]) * jax.nn.softplus(ab[:, :h] + W["dt_bias"])
    o = _gd.gdn_scan(
        q.transpose(1, 0, 2).astype(cdt), k.transpose(1, 0, 2).astype(cdt),
        _heads_first(qkv[:, 2 * h * dk:], h, cdt), g.T, beta.T, seg_start,
        n_real=n_real, interpret=interpret)
    o = rms_norm(o.transpose(1, 0, 2), W["o_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(_mm(x, W["gate"])).reshape(t, h, dv)
    return _mm((o * gate).reshape(t, h * dv), W["o"])


def _full_mixer(cfg, W, x, seg_start, interpret):
    t, d = x.shape
    h = cfg.num_attention_heads
    cdt = W["o"].dtype
    qkv = _mm(x, W["qkv"])
    q = rms_norm(qkv[:, :d], W["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(qkv[:, d:2 * d], W["k_norm"], cfg.rms_norm_eps)
    o = packed_causal_attention(
        _heads_first(q, h, cdt), _heads_first(k, h, cdt),
        _heads_first(qkv[:, 2 * d:], h, cdt), seg_start,
        interpret=interpret)
    return _mm(o.transpose(1, 0, 2).reshape(t, d), W["o"])


def trunk(cfg: GDNHybridConfig, P: dict, tokens, positions, seg_start, *,
          n_real=None, interpret: Optional[bool] = None):
    """The block stack over a packed token axis: the residual stream
    (T, hidden) f32 BEFORE the final norm.  ``n_real``: the axis is padding
    from there on, and the linear layers' scan stops at that chunk (the
    padded tokens' rows of the result are then no model's output)."""
    eps, period = cfg.rms_norm_eps, cfg.period
    stacked = {name: v for name, v in P.items() if name[0] == "S"}

    def one_period(x, layers):
        for j, kind in enumerate(period):
            pre = f"S{j}."
            W = {name[len(pre):]: v for name, v in layers.items()
                 if name.startswith(pre)}
            if kind == LINEAR:
                y = _linear_mixer(cfg, W, x, positions, seg_start, n_real,
                                  interpret)
            else:
                y = _full_mixer(cfg, W, x, seg_start, interpret)
            x = x + rms_norm(y, W["attn_norm"], eps)
            y = _swiglu(x, W["w1"], W["w3"], W["w2"])
            x = x + rms_norm(y, W["ffn_norm"], eps)
        return x, None

    x = P["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(one_period, x, stacked)
    return x


def forward_packed(cfg: GDNHybridConfig, P: dict, tokens, positions,
                   seg_start, last_idx, k: int, *,
                   interpret: Optional[bool] = None,
                   score_backend: Optional[str] = None) -> dict:
    """One dispatch: the packed token axis through the trunk, each row's
    last position through the final norm, and its top-``k`` items taken on
    the device.  Arguments as ``latent_moe.forward_packed`` (no ``valid``:
    a padded token is a one-event history that nothing reads).  Returns
    ``values`` and ``indices`` (R, k), ``h_last`` (R, hidden) bf16 and, on
    the fused score backend, the merge counters."""
    # `pack` lays rows end to end from token 0 and a padded row repeats row
    # 0, so the last real token is the largest of `last_idx`
    x = trunk(cfg, P, tokens, positions, seg_start,
              n_real=jnp.max(last_idx) + 1, interpret=interpret)
    return score_head(P, cfg.vocab_size, cfg.rms_norm_eps, x[last_idx], k,
                      interpret=interpret, score_backend=score_backend)


def forward_flat(cfg: GDNHybridConfig, P: dict, flat, t_pad: int, k: int,
                 **kw) -> dict:
    """:func:`forward_packed` on ``latent_moe.flatten``'s layout."""
    tokens, positions, seg_start = (
        flat[i * t_pad:(i + 1) * t_pad] for i in range(3))
    return forward_packed(cfg, P, tokens, positions, seg_start,
                          flat[4 * t_pad:], k, **kw)


class DispatchCounters:
    """This family's own counters in the packed scorer: what the scan of
    the linear layers was asked (real tokens and rows, one state a row a
    layer) and what it ran (the chunks up to the last real token: alignment
    to chunks included, a rung's padded tail not)."""

    fetch = ()

    def __init__(self, config: GDNHybridConfig):
        self.config = config
        self.scan_tokens = 0
        self.scan_rows = 0
        self.scan_chunks = 0

    def add(self, t_pad: int, n_rows: int, n_tokens: int, got: dict) -> None:
        layers = self.config.n_linear_layers
        self.scan_tokens += layers * n_tokens
        self.scan_rows += layers * n_rows
        self.scan_chunks += layers * _gd.scan_chunks(t_pad, n_real=n_tokens)

    def stats(self) -> dict:
        return {
            "linear_layers": self.config.n_linear_layers,
            "full_layers": (self.config.num_hidden_layers
                            - self.config.n_linear_layers),
            "scan_chunk": _gd.CHUNK,
            "scan_tokens": self.scan_tokens,
            "scan_rows": self.scan_rows,
            "scan_chunks": self.scan_chunks,
        }


@dataclasses.dataclass
class GDNHybridModel:
    """What the sequence template serves: the config, the parameter pytree
    (device-resident, or NumPy after a pickle round trip), the item id map,
    and optionally where histories come from (``histories``; None = the
    event store)."""

    config: GDNHybridConfig
    params: dict
    item_map: object
    histories: object = None


Config, Model = GDNHybridConfig, GDNHybridModel
